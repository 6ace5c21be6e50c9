"""DPL008 — thread-escape: unlocked shared-state writes in pool workers.

The prefetch/encode pools (ops/streaming.py, parallel/sharded.py) and the
span sinks (obs/trace.py, re-exported by the profiler) share objects
between the pipeline thread and worker threads. The audited handoffs are
(a) the sink lock (``_add_stage_time`` under ``_sink_lock``) and (b) the
adopt/merge protocol (``adopt_sinks(parent_sinks)`` installing a
parent's collectors before any recording). A worker callable that *writes* an
attribute or container element of a captured object the enclosing scope
also touches — outside any lock and outside the adopt handoff — is a data
race: torn stage timings at best, a corrupted slab index feeding the DP
kernel at worst.

Detection is per scope (flow/summary.py): callables handed to
``executor.submit`` / ``executor.map`` / ``threading.Thread(target=...)``
are workers; their free variables are the captured state; writes
(attribute/element assignment, mutator methods, ``nonlocal`` rebinds) to
names the enclosing scope also references must sit inside a ``with``
block on a lock-ish object or inside ``adopt_sinks``.
"""

from __future__ import annotations

from typing import Iterable, List

from pipelinedp_tpu.lint.engine import Finding, ProjectContext, ProjectRule


class ThreadEscapeRule(ProjectRule):
    rule_id = "DPL008"
    name = "thread-escape"
    description = ("A pool-worker callable writes state shared with the "
                   "enclosing scope without a lock or the adopt_sinks "
                   "handoff.")
    hint = ("Guard the write with `with <lock>:`, route timings through "
            "profiler.adopt_sinks/_add_stage_time, or hand the worker an "
            "immutable snapshot and merge results on the pipeline "
            "thread.")

    def check_project(self, project: ProjectContext) -> Iterable[Finding]:
        flow = project.flow
        findings: List[Finding] = []
        for qual, fsum in flow.functions.items():
            if not fsum.hazards:
                continue
            module = flow.function_module[qual]
            relpath = project.relpath_of(module)
            for hz in fsum.hazards:
                findings.append(Finding(
                    self.rule_id, relpath, hz.line, hz.col,
                    f"pool worker `{hz.worker}` performs an unguarded "
                    f"{hz.write} on captured `{hz.name}`, which the "
                    f"enclosing scope also touches (line "
                    f"{hz.shared_line}) — cross-thread write without the "
                    f"lock or an adopt/merge handoff",
                    self.hint))
        return findings
