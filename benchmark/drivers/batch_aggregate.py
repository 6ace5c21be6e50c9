"""Back-to-back cold batch releases.

Each aggregate is what a PipelineDP user runs for one release: a fresh
``NaiveBudgetAccountant`` and ``JaxDPEngine`` over the host columns, from
``aggregate`` to ``to_columns()``, with kernel seed i of the run. The
window starts aggregates until ``seconds`` have passed and ends when the
one in flight finishes, so it holds whole aggregates and may overrun
``seconds`` by up to one.
"""

from __future__ import annotations

import time

from benchmark import common


class Driver:

    def __init__(self, cfg: dict, traffic: dict, columns,
                 seeds: common.Seeds):
        import pipelinedp_tpu as pdp
        pid, pk, value = columns
        self._data = pdp.ColumnarData(pid=pid, pk=pk, value=value)
        self._query = cfg["aggregate"]
        self._seeds = seeds
        self.n_rows = len(pid)

    def setup(self) -> None:
        """One aggregate of the same shape: loads or compiles every
        program the window runs."""
        common.release_of_aggregate(self._data, self._query,
                                    self._seeds.engine(-1))

    def window(self, seconds: float) -> common.Window:
        out = common.Window()
        t0 = time.perf_counter()
        i = 0
        while True:
            item = common.Item(work=self.n_rows)
            seed = self._seeds.engine(i)
            common.run_item(
                item, out, t0, "bench/aggregate",
                lambda: (self._query, common.release_of_aggregate(
                    self._data, self._query, seed)))
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        out.seconds = time.perf_counter() - t0
        return out

    def close(self) -> None:
        self._data = None
