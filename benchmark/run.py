"""Runs one cell of the benchmark once, on the chips of this machine.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` at the checkout's
root: the cell's configuration file (sizes, DP parameters and the data
generator under ``benchmark/generators/``), its traffic mix
(``benchmark/traffic/<traffic>.json``, which names its driver under
``benchmark/drivers/``), the limits of its correctness numbers
(``benchmark/limits/<cell>.json``) and one reader per metric
(``benchmark/metrics/<metric>.py``).

A run makes its data from ``--seed``, sets up (loading or compiling
every program the window uses), measures a window of ``--seconds``, then
judges every release of the window against the plain reference
(``benchmark/reference.py``). With ``--trace 1`` the window runs under
the JAX profiler and the per-layer metrics are read from the trace and
the program's stage times; with ``--trace 0`` the end-to-end metrics are
reported. The last line of standard output is one JSON object; the last
lines of standard error are the numbers compared, each with its limit.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 2 and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import resource
import shutil
import sys
import tempfile
import time
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _process_age_s() -> float:
    """Seconds since this process started (Linux /proc), so that set-up
    counts the interpreter's start too."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_reader(name: str):
    """The reader module of one metric: ``benchmark/metrics/<name>.py``."""
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""
    config: dict
    window: object
    setup_s: float
    trace: Optional[object]
    peaks: dict


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    active: a window should have none."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _listen(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False


def peak_rss_bytes() -> int:
    """The process's peak resident memory so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.stderr.write(f"benchmark: no TPU (JAX reports "
                         f"{devices[0].platform!r}); refusing to run\n")
        return None
    if len(devices) < n:
        sys.stderr.write(f"benchmark: the cell needs {n} TPU chips, JAX "
                         f"reports {len(devices)}\n")
        return None
    return devices[:n]


@dataclasses.dataclass
class Cell:
    """One workload with everything it names, resolved from the files."""
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    peaks: dict


def resolve(bench: dict, name: str, device_kind: str) -> Cell:
    workload = find(bench["workloads"], name, "workload")
    cfg = load_json(os.path.join(
        ROOT, find(bench["configs"], workload["config"], "config")["file"]))
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if device_kind not in peaks:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return Cell(
        workload=workload, config=cfg,
        traffic=load_json(os.path.join(HERE, "traffic",
                                       f"{workload['traffic']}.json")),
        limits=load_json(os.path.join(HERE, "limits",
                                      f"{name}.json"))["limits"],
        peaks=peaks[device_kind])


def main(argv=None) -> int:
    t_start = time.perf_counter() - _process_age_s()
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    workload = find(bench["workloads"], args.workload, "workload")
    devices = require_chips(int(workload["chips"]))
    if devices is None:
        return 2
    cell = resolve(bench, args.workload, devices[0].device_kind)
    # Before the first encode: a failed native build raises instead of
    # quietly running the numpy twin.
    os.environ["PIPELINEDP_TPU_REQUIRE_NATIVE"] = "1"
    from pipelinedp_tpu import compile_cache
    compile_cache.configure(ROOT)
    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    result = run_cell(cell, [m for m in wanted if applies(m, args.workload)],
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices,
                      t_start=t_start)
    for name, c in result["checks"].items():
        sys.stderr.write(f"check {name} = {c['value']!r} "
                         f"(limit {c['limit']!r})\n")
    print(json.dumps(result), flush=True)
    return 0


def judge_window(window, columns, limits: dict):
    """(correct, readings) of every release of the window against the
    plain reference, which sees only the columns."""
    from benchmark import reference

    pairs = reference.Pairs(*columns)
    judge = reference.Judge()
    expectations = {}
    for q, rel in window.releases:
        key = json.dumps(q, sort_keys=True)
        if key not in expectations:
            expectations[key] = reference.expect(
                pairs, reference.Query.from_dict(q))
        judge.add(expectations[key], rel)
    readings = judge.readings()
    correct = reference.verdict(readings, limits, len(window.items),
                                judge.n_releases)
    return correct, readings


def run_cell(cell: Cell, metric_defs, *, seed: int, seconds: float,
             trace: bool, devices, t_start: float) -> dict:
    """Set-up, window, judgement and metrics of one run: the result line
    as a dict, with the numbers compared under ``checks``, last."""
    import jax

    from benchmark import common, trace_reduce

    cfg, traffic = cell.config, cell.traffic
    generator = importlib.import_module(
        f"benchmark.generators.{cfg['generator']}")
    driver_module = importlib.import_module(
        f"benchmark.drivers.{traffic['driver']}")

    columns = generator.make_columns(cfg, seed)
    driver = driver_module.Driver(cfg, traffic, columns, common.Seeds(seed))
    driver.setup()
    setup_s = time.perf_counter() - t_start
    rss = {"setup": peak_rss_bytes()}

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with CompileCounter() as compiles, \
                    jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                window = driver.window(seconds)
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devices)
        rss["window"] = peak_rss_bytes()
        driver.close()
        del driver
        reduced = (trace_reduce.reduce(trace_reduce.find_xplane(trace_dir),
                                       n_devices=len(devices))
                   if trace_dir else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    t_ref = time.perf_counter()
    correct, readings = judge_window(window, columns, cell.limits)
    rss["reference"] = peak_rss_bytes()
    failed = sum(not it.ok for it in window.items)

    ctx = Context(config=cfg, window=window, setup_s=setup_s,
                  trace=reduced, peaks=cell.peaks)
    metrics = {}
    for m in metric_defs:
        value = load_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    for it in window.items:
        if not it.ok:
            sys.stderr.write(f"failed at {it.start:.3f}s: {it.error}\n")
    sys.stderr.write(json.dumps({
        "items": len(window.items), "failed": failed,
        "window_s": window.seconds, "compiles_in_window": compiles.count,
        "reference_s": time.perf_counter() - t_ref,
        "host_peak_rss_bytes": rss,
        "item_s": [it.end - it.start for it in window.items][:60],
        "readings": readings,
    }) + "\n")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(window.items),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["checks"] = {k: {"value": readings[k], "limit": v}
                        for k, v in cell.limits.items()}
    return result


if __name__ == "__main__":
    sys.exit(main())
