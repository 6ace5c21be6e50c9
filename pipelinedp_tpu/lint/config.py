"""dplint configuration: which modules are exempt from which rules.

The default stance is deny-by-default: every scanned module is treated as
privacy-critical unless a pattern below says otherwise. Exemptions are
*narrow and documented* — each entry names the structural reason the rule
does not apply there. Tests construct custom configs to exercise rules in
isolation.
"""

from __future__ import annotations

import dataclasses
import fnmatch
from typing import Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class LintConfig:
    """Per-rule module exemptions (fnmatch patterns over dotted modules)."""

    # DPL004 — modules allowed to reference numpy/stdlib RNGs.
    #  * noise_core: the declared seedable numpy fallback sampler
    #    (noise_core.py `_fallback_*`) — distributionally equivalent,
    #    documented weaker bit-level guarantees, test-reseedable.
    #  * analysis / dataset_histograms: utility-analysis tooling; estimates
    #    error on non-released intermediates, not on the DP release path.
    insecure_rng_exempt: Tuple[str, ...] = (
        "pipelinedp_tpu.noise_core",
        "pipelinedp_tpu.analysis.*",
        "pipelinedp_tpu.dataset_histograms.*",
        "pipelinedp_tpu.lint.*",
    )

    # DPL002 — the mechanism-primitive layer: these modules *are* the noise
    # sinks; their scales/eps/delta arrive pre-calibrated from MechanismSpecs
    # resolved upstream (jax_engine/dp_computations read the specs and pass
    # scalars down).
    unaccounted_noise_exempt: Tuple[str, ...] = (
        "pipelinedp_tpu.noise_core",
        "pipelinedp_tpu.ops.noise",
        "pipelinedp_tpu.ops.selection",
        "pipelinedp_tpu.ops.quantiles",
        "pipelinedp_tpu.partition_selection",
        "pipelinedp_tpu.quantile_tree",
        "pipelinedp_tpu.native.*",
        "pipelinedp_tpu.lint.*",
    )

    # DPL005 — modules whose job is budget arithmetic: the accountant
    # itself, and dp_computations.equally_split_budget (the sanctioned
    # splitter the reference uses for MEAN/VARIANCE internal splits).
    budget_literal_exempt: Tuple[str, ...] = (
        "pipelinedp_tpu.budget_accounting",
        "pipelinedp_tpu.dp_computations",
        "pipelinedp_tpu.pld",
        "pipelinedp_tpu.lint.*",
    )

    # DPL007 — the mechanism-primitive and host-encode layer dpflow
    # trusts as *opaque*: handling raw private columns is these modules'
    # job, their host materializations are mechanism-internal (never a
    # release), and exposures must not propagate to callers. Everything
    # else — the orchestration layer (jax_engine, dp_engine, runtime,
    # backends, dataframes) — is analyzed.
    release_taint_trusted: Tuple[str, ...] = (
        "pipelinedp_tpu.noise_core",
        "pipelinedp_tpu.ops.noise",
        "pipelinedp_tpu.ops.selection",
        "pipelinedp_tpu.ops.quantiles",
        "pipelinedp_tpu.ops.columnar",
        "pipelinedp_tpu.ops.encoding",
        "pipelinedp_tpu.ops.wirecodec",
        "pipelinedp_tpu.contribution_bounders",
        "pipelinedp_tpu.partition_selection",
        "pipelinedp_tpu.quantile_tree",
        "pipelinedp_tpu.data_extractors",
        "pipelinedp_tpu.native.*",
        "pipelinedp_tpu.dataset_histograms.*",
        "pipelinedp_tpu.analysis.*",
        "pipelinedp_tpu.lint.*",
    )

    # DPL011 — telemetry-taint exemptions: the obs package itself (its
    # job is building the records from already-validated scalars; the
    # API-level check_safe_value gate plus its own tests are the
    # control there) and the lint tree.
    telemetry_taint_trusted: Tuple[str, ...] = (
        "pipelinedp_tpu.obs.*",
        "pipelinedp_tpu.lint.*",
    )

    # DPL012 — durable-write discipline exemptions. Unlike the module
    # patterns above these match *function qualnames* (module + in-module
    # dotted path) because the verdict is per-transaction, not per-file.
    #  * JsonlWal internals: the append discipline IS the durability
    #    protocol — one long-lived 'ab' handle, every record
    #    write+flush+fsync'd; rewrite/recover manage that handle.
    #  * flight-recorder spool: flush-only by design (obs/flight.py) —
    #    an fsync per appended event would serialize the hot path, and
    #    the crash spool tolerates losing the final buffered lines.
    #  * ops_plane._writable: the /healthz writability probe creates and
    #    unlinks a throwaway file; durability is the question it asks,
    #    not a property it needs.
    #  * profiler/lint: operator-facing report and cache
    #    artifacts — loss is repaired by re-running the tool.
    atomic_write_exempt: Tuple[str, ...] = (
        "pipelinedp_tpu.runtime.journal.JsonlWal.*",
        "pipelinedp_tpu.obs.flight.FlightRecorder.bind_spool",
        "pipelinedp_tpu.obs.flight.FlightRecorder._rotate_spool_locked",
        "pipelinedp_tpu.obs.ops_plane._writable",
        "pipelinedp_tpu.profiler.*",
        "pipelinedp_tpu.lint.*",
    )

    # DPL013 — transactions whose pre-commit durability is itself the
    # protocol (none in-tree today; the tuple exists so a future
    # write-behind cache documents its contract here instead of
    # sprinkling suppressions through strict-gated trees).
    commit_ordering_trusted: Tuple[str, ...] = ()

    # DPL014 — canonical lock names whose *contract* is "the lock
    # serializes the durable append", so holding them across the WAL
    # fsync is the design, not an inversion:
    #  * live-session append lock: the append transaction (payload save
    #    -> WAL record -> fold) must be serialized end-to-end or two
    #    appends could commit records out of payload order.
    #  * audit-trail lock: audit records are ordered by the lock; the
    #    fsync under it is what makes "ordered" mean anything on disk.
    lock_scope_exempt: Tuple[str, ...] = (
        "pipelinedp_tpu.serving.live.LiveDatasetSession._append_lock",
        "pipelinedp_tpu.obs.audit.AuditTrail._lock",
    )

    # DPL015 — function qualnames allowed nondeterminism primitives on
    # release paths:
    #  * ops.noise / ops.selection / ops.finalize: the blessed compiled
    #    entries — their jnp arithmetic traces under jit into one XLA
    #    program, which is exactly the determinism contract.
    #  * JaxDPEngine._legacy_finalize: the unfused eager parity oracle,
    #    pinned bit-identical to the fused path by finalize tests.
    #  * lint itself analyzes release code without being on the path.
    release_determinism_exempt: Tuple[str, ...] = (
        "pipelinedp_tpu.ops.noise.*",
        "pipelinedp_tpu.ops.selection.*",
        "pipelinedp_tpu.ops.finalize.*",
        "pipelinedp_tpu.jax_engine.JaxDPEngine._legacy_finalize",
        "pipelinedp_tpu.lint.*",
    )

    @staticmethod
    def _matches(module: str, patterns: Sequence[str]) -> bool:
        return any(fnmatch.fnmatch(module, p) for p in patterns)

    def is_insecure_rng_exempt(self, module: str) -> bool:
        return self._matches(module, self.insecure_rng_exempt)

    def is_unaccounted_noise_exempt(self, module: str) -> bool:
        return self._matches(module, self.unaccounted_noise_exempt)

    def is_budget_literal_exempt(self, module: str) -> bool:
        return self._matches(module, self.budget_literal_exempt)

    def is_release_taint_trusted(self, module: str) -> bool:
        return self._matches(module, self.release_taint_trusted)

    def is_telemetry_taint_trusted(self, module: str) -> bool:
        return self._matches(module, self.telemetry_taint_trusted)

    def is_atomic_write_exempt(self, qualname: str) -> bool:
        return self._matches(qualname, self.atomic_write_exempt)

    def is_commit_ordering_trusted(self, qualname: str) -> bool:
        return self._matches(qualname, self.commit_ordering_trusted)

    def is_lock_scope_exempt(self, lock_name: str) -> bool:
        return self._matches(lock_name, self.lock_scope_exempt)

    def is_release_determinism_exempt(self, qualname: str) -> bool:
        return self._matches(qualname, self.release_determinism_exempt)


DEFAULT_CONFIG = LintConfig()
