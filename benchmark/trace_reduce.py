"""The one reduction from a JAX profiler trace to device metrics.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``. On a TPU the trace holds one plane per chip
(``/device:TPU:<n>``) whose ``XLA Ops`` line has an event per operation
and whose ``XLA Modules`` line has an event per program execution, named
``jit_<function>(<fingerprint>)``; and a ``/host:CPU`` plane whose thread
lines carry the host's ``TraceAnnotation`` spans, among them the
program's ``dp/...`` stages and the benchmark's ``bench/...`` spans. Both
planes share one clock.

From these it gives, within the benchmark's window span:

* busy time: the union of the operation intervals of each chip,
  averaged over chips;
* device seconds per program (fingerprint dropped), summed over chips;
* idle gaps: the complement of the busy union, each labelled with the
  innermost ``dp/`` or ``bench/`` span open at its midpoint.
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench/window"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
SPAN_PREFIXES = ("dp/", "bench/")
NO_SPAN = "(no span)"
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def program_name(event_name: str) -> str:
    """``jit__chunk_step_rle(123)`` -> ``_chunk_step_rle``."""
    name = _FINGERPRINT.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    program_s: Dict[str, float]
    idle_by_span: Dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def programs_matching(self, patterns) -> float:
        """Device seconds of the programs whose name matches any regex."""
        rx = [re.compile(p) for p in patterns]
        return sum(s for name, s in self.program_s.items()
                   if any(r.search(name) for r in rx))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def label_gaps(spans: List[Tuple[str, int, int]],
               gap_list: List[Interval]) -> List[str]:
    """The innermost (shortest) span open at each gap's midpoint."""
    order = sorted(range(len(gap_list)),
                   key=lambda i: gap_list[i][0] + gap_list[i][1])
    by_start = sorted(spans, key=lambda sp: sp[1])
    labels = [NO_SPAN] * len(gap_list)
    active: list = []
    j = 0
    for i in order:
        t = 0.5 * (gap_list[i][0] + gap_list[i][1])
        while j < len(by_start) and by_start[j][1] <= t:
            name, s, e = by_start[j]
            heapq.heappush(active, (e - s, e, name))
            j += 1
        while active and active[0][1] <= t:
            heapq.heappop(active)
        if active:
            labels[i] = active[0][2]
    return labels


def reduce_profile(profile, n_devices: Optional[int] = None) -> Reduced:
    """Reduces a ``ProfileData`` (or anything with the same planes,
    lines and events) to device metrics over the window span."""
    device_ops: Dict[str, List[Interval]] = {}
    programs: List[Tuple[str, int, int]] = []
    spans: List[Tuple[str, int, int]] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        ops.append((s, s + int(ev.duration_ns)))
                elif line.name == PROGRAMS_LINE:
                    for ev in line.events:
                        s = int(ev.start_ns)
                        programs.append((program_name(ev.name), s,
                                         s + int(ev.duration_ns)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[0]
    if not device_ops:
        raise ValueError("the trace holds no TPU plane")
    n = n_devices or len(device_ops)

    program_s: Dict[str, float] = {}
    for name, s, e in programs:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            program_s[name] = program_s.get(name, 0.0) + d * 1e-9
    busy_ns = 0
    idle_by_span: Dict[str, float] = {}
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    for ops in device_ops.values():
        busy = union(clip(ops, lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        idle = gaps(busy, lo, hi)
        for (s, e), label in zip(idle, label_gaps(inner, idle)):
            idle_by_span[label] = (idle_by_span.get(label, 0.0)
                                   + (e - s) * 1e-9 / n)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   n_devices=n, program_s=program_s,
                   idle_by_span=idle_by_span)


def reduce(path: str, n_devices: Optional[int] = None) -> Reduced:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), n_devices)
