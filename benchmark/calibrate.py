"""Readings from which a cell's correctness limits are set.

    python3 -m benchmark.calibrate --workload <cell> --seeds 1,2,... \
        [--control-seeds 7,8,9] [--seconds 1]

In one process, for each of ``--seeds``: the cell's data from that seed,
its driver (set up once per seed, so programs load once per process), a
window of ``--seconds`` at the cell's own load, and the numbers compared
(the lower readings: the largest over sound seeds). For each of
``--control-seeds``: the same data and the release a window's aggregate
would make, produced by the plain reference without contribution
bounding in the program's place (the upper readings: the smallest over
the control).
Prints one JSON line per reading. The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

import numpy as np

from benchmark import common, reference, run


def _release_of_control(cell, columns, seed):
    """The release of the cell's aggregate from the plain reference
    without bounding."""
    q = cell.config["aggregate"]
    return q, reference.release(reference.Pairs(*columns),
                                reference.Query.from_dict(q),
                                np.random.default_rng(seed), bound=False)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=1.0)
    args = p.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]

    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    workload = run.find(bench["workloads"], args.workload, "workload")
    devices = run.require_chips(int(workload["chips"]))
    if devices is None:
        return 2
    cell = run.resolve(bench, args.workload, devices[0].device_kind)
    os.environ["PIPELINEDP_TPU_REQUIRE_NATIVE"] = "1"
    from pipelinedp_tpu import compile_cache
    compile_cache.configure(run.ROOT)
    generator = importlib.import_module(
        f"benchmark.generators.{cell.config['generator']}")
    driver_module = importlib.import_module(
        f"benchmark.drivers.{cell.traffic['driver']}")

    def emit(**kw):
        print(json.dumps(kw), flush=True)

    for seed in ints(args.seeds):
        t0 = time.perf_counter()
        columns = generator.make_columns(cell.config, seed)
        driver = driver_module.Driver(cell.config, cell.traffic, columns,
                                      common.Seeds(seed))
        driver.setup()
        t1 = time.perf_counter()
        window = driver.window(args.seconds)
        t2 = time.perf_counter()
        driver.close()
        del driver
        correct, readings = run.judge_window(window, columns, cell.limits)
        emit(kind="sound", seed=seed, correct=correct, readings=readings,
             items=len(window.items),
             failed=sum(not it.ok for it in window.items),
             setup_s=t1 - t0, window_s=t2 - t1,
             judge_s=time.perf_counter() - t2,
             host_peak_rss_bytes=run.peak_rss_bytes())

    for seed in ints(args.control_seeds):
        columns = generator.make_columns(cell.config, seed)
        window = common.Window(items=[common.Item(work=0, ok=True)],
                               releases=[_release_of_control(cell, columns,
                                                             seed)])
        correct, readings = run.judge_window(window, columns, cell.limits)
        emit(kind="control", seed=seed, correct=correct, readings=readings,
             items=len(window.items))
    return 0


if __name__ == "__main__":
    sys.exit(main())
