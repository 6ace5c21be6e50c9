"""The plain reference that decides ``correct``.

A DP release is random, so it cannot equal a reference bit for bit. What
the reference knows exactly is the law of each released number under the
semantics the configuration states (PipelineDP's): every privacy unit
keeps a uniform sample of ``l0`` of its partitions and, in each, a
uniform sample of ``linf`` of its rows with values clipped to
[min_value, max_value]; the budget is split equally over the metrics and
private partition selection (delta only over mechanisms that use it);
Laplace noise of scale l0 * linf_sensitivity / eps or analytic-Gaussian
noise for sqrt(l0) * linf_sensitivity; truncated-geometric partition
selection (Desfontaines, Voss and Lam, PoPETs 2022) on the bounded count
of privacy units.

From the rows alone it computes, per partition, the mean and variance of
each bounded metric, the noise variance and the probability of release.
A release is then judged by three kinds of numbers:

* ``z_max``: the largest |released - mean| / sd over every released value;
* ``z_sd_gap`` and ``z_sd_gap_top``: how far the spread of those scores is
  from 1, over all released values and over the ``TOP`` partitions of
  each release with the largest expected count, where a lost or doubled
  share of the rows stands out of the noise;
* ``kept_gap``: the largest |released partitions - expected| / sd.

Nothing here imports the program under test or takes anything it made.
The plain release (``release``) is the same semantics computed directly;
with ``bound=False`` it skips contribution bounding, which is the control
that must come out not correct.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import math
import os
from typing import Dict, List, Optional

import numpy as np
from scipy import special

METRICS = ("COUNT", "SUM", "PRIVACY_ID_COUNT")
# Partitions per release whose scores make up z_sd_gap_top.
TOP = 100
# Half-width, in standard deviations, of the grid over which the
# expected keep probability of a partition is summed.
GRID_SD = 8.0
# Threads for the pair table; each holds a block's sort in memory.
THREADS = 8


@dataclasses.dataclass(frozen=True)
class Query:
    """One aggregate as the configuration states it."""
    metrics: tuple
    noise_kind: str
    epsilon: float
    delta: float
    l0: int
    linf: int
    lo: float
    hi: float

    @classmethod
    def from_dict(cls, d: dict) -> "Query":
        metrics = tuple(d["metrics"])
        if not set(metrics) <= set(METRICS):
            raise ValueError(f"the reference knows only {METRICS}")
        return cls(metrics=metrics, noise_kind=d["noise_kind"],
                   epsilon=float(d["epsilon"]), delta=float(d["delta"]),
                   l0=int(d["max_partitions_contributed"]),
                   linf=int(d["max_contributions_per_partition"]),
                   lo=float(d["min_value"]), hi=float(d["max_value"]))


def analytic_gaussian_sigma(eps: float, delta: float, l2: float) -> float:
    """Smallest sigma of the analytic Gaussian mechanism (Balle and Wang,
    ICML 2018, Theorem 8) for this (eps, delta) and l2 sensitivity."""

    def delta_of(sigma):
        a = l2 / (2 * sigma)
        b = eps * sigma / l2
        return (special.ndtr(a - b)
                - math.exp(eps) * special.ndtr(-a - b))

    lo, hi = 1e-6 * l2, l2
    while delta_of(hi) > delta:
        hi *= 2
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if delta_of(mid) > delta:
            lo = mid
        else:
            hi = mid
    return hi


def budgets(q: Query):
    """(eps, delta) of each metric's mechanism and of selection."""
    n_eps = len(q.metrics) + 1
    n_delta = (len(q.metrics) if q.noise_kind == "GAUSSIAN" else 0) + 1
    eps = q.epsilon / n_eps
    delta = q.delta / n_delta
    metric_delta = delta if q.noise_kind == "GAUSSIAN" else 0.0
    return (eps, metric_delta), (eps, delta)


def noise_sd(q: Query) -> Dict[str, float]:
    """Standard deviation of each metric's noise."""
    (eps, delta), _ = budgets(q)
    linf_sens = {"COUNT": q.linf,
                 "SUM": max(abs(q.lo), abs(q.hi)) * q.linf,
                 "PRIVACY_ID_COUNT": 1}
    out = {}
    for m in q.metrics:
        if q.noise_kind == "LAPLACE":
            out[m] = math.sqrt(2.0) * q.l0 * linf_sens[m] / eps
        else:
            out[m] = analytic_gaussian_sigma(
                eps, delta, math.sqrt(q.l0) * linf_sens[m])
    return out


def keep_probability(q: Query, n_max: int) -> np.ndarray:
    """P(release) for 0..n_max bounded privacy units: the truncated
    geometric recurrence with per-partition eps/l0 and the delta that
    composes to the selection delta over l0 partitions."""
    _, (eps, delta) = budgets(q)
    e = eps / q.l0
    d = -math.expm1(math.log1p(-delta) / q.l0)
    pi = np.zeros(n_max + 1)
    grow, shrink = math.exp(e), math.exp(-e)
    p = 0.0
    for n in range(1, n_max + 1):
        p = min(grow * p + d, 1.0 - shrink * (1.0 - p - d), 1.0)
        pi[n] = p
        if p == 1.0:
            pi[n:] = 1.0
            break
    return pi


class _Block:
    """The (privacy unit, partition) pairs of one range of privacy units:
    row count, clipped value sums, and each unit's number of partitions."""

    def __init__(self, pid, pk, value, n_keys: int):
        key = pid.astype(np.int64) * n_keys + pk
        order = np.argsort(key)
        key = key[order]
        first = np.empty(len(key), dtype=bool)
        first[:1] = True
        np.not_equal(key[1:], key[:-1], out=first[1:])
        starts = np.flatnonzero(first)
        del first
        self.starts = starts.astype(np.int32)
        self.n = np.diff(np.append(starts, len(key))).astype(np.int32)
        self.pk = (key[starts] % n_keys).astype(np.int32)
        unit = key[starts] // n_keys
        del key, starts
        unit_first = np.empty(len(unit), dtype=bool)
        unit_first[:1] = True
        np.not_equal(unit[1:], unit[:-1], out=unit_first[1:])
        del unit
        per_unit = np.diff(np.append(np.flatnonzero(unit_first),
                                     len(unit_first)))
        self.d = np.repeat(per_unit.astype(np.int32), per_unit)
        self.unit_first = unit_first
        self.value = (None if value is None
                      else value[order].astype(np.float32))
        self._clip = None

    def sums(self, lo: float, hi: float):
        if self._clip != (lo, hi):
            v = np.clip(self.value, lo, hi).astype(np.float64)
            self._sums = (np.add.reduceat(v, self.starts),
                          np.add.reduceat(v * v, self.starts))
            self._clip = (lo, hi)
        return self._sums

    def moments(self, q: Query, n_keys: int) -> Dict[str, np.ndarray]:
        """Per-key sums, over this block's pairs, of each bounded metric's
        mean and variance and of the kept-unit count's."""
        keep = np.minimum(1.0, q.l0 / self.d)
        rows = np.minimum(self.n, q.linf).astype(np.float64)

        def per_key(w):
            return np.bincount(self.pk, weights=w, minlength=n_keys)

        out = {"units": np.bincount(self.pk, minlength=n_keys),
               "PRIVACY_ID_COUNT": per_key(keep),
               "PRIVACY_ID_COUNT_var": per_key(keep * (1 - keep))}
        if "COUNT" in q.metrics:
            out["COUNT"] = per_key(keep * rows)
            out["COUNT_var"] = per_key(keep * (1 - keep) * rows * rows)
        if "SUM" in q.metrics:
            s1, s2 = self.sums(q.lo, q.hi)
            n = self.n.astype(np.float64)
            pair_mean = rows * s1 / n
            spread = np.maximum(s2 / n - (s1 / n)**2, 0.0)
            pair_var = np.where(
                n > 1, rows * spread * (n - rows) / np.maximum(n - 1, 1),
                0.0)
            out["SUM"] = per_key(keep * pair_mean)
            out["SUM_var"] = per_key(keep * (pair_var + pair_mean**2)
                                     - (keep * pair_mean)**2)
        return out


class Pairs:
    """The rows grouped by (privacy unit, partition), in ``BLOCKS``
    ranges of privacy units that threads build and read side by side.
    Built once per dataset; each query then costs a few passes over the
    pairs."""

    BLOCKS = 16

    def __init__(self, pid: np.ndarray, pk: np.ndarray,
                 value: Optional[np.ndarray]):
        pid = np.asarray(pid)
        pk = np.asarray(pk)
        if pk.min() < 0 or pid.min() < 0:
            raise ValueError("the reference expects non-negative ids")
        self.n_keys = int(pk.max()) + 1
        lo = int(pid.min())
        span = int(pid.max()) - lo + 1
        block = ((pid.astype(np.int64) - lo) * self.BLOCKS // span).astype(
            np.uint8)
        order = np.argsort(block, kind="stable")
        bounds = np.zeros(self.BLOCKS + 1, dtype=np.int64)
        np.cumsum(np.bincount(block, minlength=self.BLOCKS), out=bounds[1:])
        del block
        value = None if value is None else np.asarray(value)

        def build(b):
            rows = order[bounds[b]:bounds[b + 1]]
            return _Block(pid[rows], pk[rows],
                          None if value is None else value[rows],
                          self.n_keys)

        self.blocks = _parallel(build, range(self.BLOCKS))
        self.present = np.bincount(pk, minlength=self.n_keys) > 0


def _parallel(fn, items, reduce=None):
    """fn over items on up to THREADS threads (numpy releases the GIL):
    the list of results, or their running reduction."""
    with concurrent.futures.ThreadPoolExecutor(
            min(THREADS, os.cpu_count() or 1)) as pool:
        results = pool.map(fn, items)
        if reduce is None:
            return list(results)
        total = next(results)
        for r in results:
            total = reduce(total, r)
        return total


@dataclasses.dataclass
class Expectation:
    """Per-partition law of one query's release, indexed by key."""
    query: Query
    mean: Dict[str, np.ndarray]
    var: Dict[str, np.ndarray]
    keep: np.ndarray
    present: np.ndarray


def expect(pairs: Pairs, q: Query) -> Expectation:
    """Mean and variance of each bounded metric, plus noise variance, and
    the probability that each partition is released."""
    total = _parallel(lambda b: b.moments(q, pairs.n_keys), pairs.blocks,
                      lambda a, b: {k: a[k] + b[k] for k in a})
    sd = noise_sd(q)
    mean = {m: total[m] for m in q.metrics}
    var = {m: total[m + "_var"] + sd[m]**2 for m in q.metrics}
    keep = _expected_keep(q, total["PRIVACY_ID_COUNT"],
                          total["PRIVACY_ID_COUNT_var"], total["units"])
    keep[~pairs.present] = 0.0
    return Expectation(query=q, mean=mean, var=var, keep=keep,
                       present=pairs.present)


def _expected_keep(q: Query, mu: np.ndarray, var: np.ndarray,
                   units: np.ndarray) -> np.ndarray:
    """E[pi(N)] for N with this mean and variance and at most ``units``
    values, N taken as normal. Up to the recurrence's crossover pi_n =
    d (e^{n e} - 1) / (e^e - 1), whose mean over a normal N is closed
    form; past saturation pi is 1; a partition whose grid of +-GRID_SD
    standard deviations straddles either is summed over a discretised
    normal."""
    pi = keep_probability(q, int(units.max()) + 1)
    _, (eps, delta) = budgets(q)
    e = eps / q.l0
    d = -math.expm1(math.log1p(-delta) / q.l0)
    n_sat = int(np.argmax(pi >= 1.0)) if pi[-1] >= 1.0 else len(pi)
    with np.errstate(over="ignore"):
        closed = d * np.expm1(np.arange(n_sat) * e) / math.expm1(e)
    off = ~np.isclose(pi[:n_sat], closed, rtol=1e-9, atol=0.0)
    n_cross = int(np.argmax(off)) - 1 if off.any() else n_sat
    sd = np.sqrt(var)
    lo = np.maximum(np.floor(mu - GRID_SD * sd), 0).astype(np.int64)
    hi = np.minimum(np.ceil(mu + GRID_SD * sd), units).astype(np.int64)
    out = np.ones(len(mu))
    below = hi <= n_cross
    out[below] = d * np.expm1(mu[below] * e + var[below] * e * e / 2
                              ) / math.expm1(e)
    band = np.flatnonzero(~below & (lo < n_sat))
    width = int((hi[band] - lo[band]).max()) + 1 if len(band) else 0
    for chunk in np.array_split(band, max(1, len(band) * width // 2**22)):
        if not len(chunk):
            continue
        grid = lo[chunk, None] + np.arange(width)[None, :]
        inside = grid <= hi[chunk, None]
        m = mu[chunk, None]
        s = np.maximum(sd[chunk, None], 1e-9)
        w = np.where(inside, special.ndtr((grid + 0.5 - m) / s)
                     - special.ndtr((grid - 0.5 - m) / s), 0.0)
        w /= w.sum(axis=1, keepdims=True)
        out[chunk] = (w * pi[np.minimum(grid, len(pi) - 1)]).sum(axis=1)
    return out


@dataclasses.dataclass
class Release:
    """What one aggregate or query released: kept keys and, per metric,
    the values in the same order."""
    keys: np.ndarray
    values: Dict[str, np.ndarray]


class Judge:
    """Accumulates the scores of several releases of one dataset."""

    def __init__(self):
        self.z_max = 0.0
        self.kept_gap = 0.0
        self._all: List[np.ndarray] = []
        self._top: List[np.ndarray] = []
        self.n_releases = 0

    def add(self, e: Expectation, r: Release) -> None:
        keys = np.asarray(r.keys, dtype=np.int64)
        if len(keys) and (keys.min() < 0 or keys.max() >= len(e.present)
                          or not e.present[keys].all()):
            raise ValueError("released a partition that is not in the data")
        if len(np.unique(keys)) != len(keys):
            raise ValueError("released a partition twice")
        if set(r.values) != set(e.query.metrics):
            raise ValueError(f"released metrics {sorted(r.values)}, "
                             f"expected {sorted(e.query.metrics)}")
        zs = []
        for m in e.query.metrics:
            v = np.asarray(r.values[m], dtype=np.float64)
            if not np.isfinite(v).all():
                self.z_max = math.inf
                v = np.nan_to_num(v, nan=1e300)
            zs.append((v - e.mean[m][keys]) / np.sqrt(e.var[m][keys]))
        z = np.stack(zs) if zs else np.zeros((0, 0))
        if z.size:
            self.z_max = max(self.z_max, float(np.abs(z).max()))
        self._all.append(z.ravel())
        first = e.query.metrics[0]
        top = np.argsort(-e.mean[first][keys], kind="stable")[:TOP]
        self._top.append(z[:, top].ravel())
        expected = float(e.keep.sum())
        sd = math.sqrt(max(float((e.keep * (1 - e.keep)).sum()), 1.0))
        self.kept_gap = max(self.kept_gap, abs(len(keys) - expected) / sd)
        self.n_releases += 1

    @staticmethod
    def _sd_gap(parts) -> float:
        z = np.concatenate(parts) if parts else np.zeros(0)
        if len(z) < 2:
            return math.inf
        return abs(float(np.sqrt(np.mean(z * z))) - 1.0)

    def readings(self) -> Dict[str, float]:
        """The numbers compared; a number that cannot be read is 1e300,
        which no limit admits."""
        out = {"z_max": self.z_max,
               "z_sd_gap": self._sd_gap(self._all),
               "z_sd_gap_top": self._sd_gap(self._top),
               "kept_gap": self.kept_gap}
        return {k: v if math.isfinite(v) else 1e300 for k, v in out.items()}


def verdict(readings: Dict[str, float], limits: Dict[str, float],
            attempted: int, compared: int) -> bool:
    """Correct when something was compared and every number is within
    its limit."""
    return (compared > 0 and compared == attempted
            and all(readings[k] <= limits[k] for k in limits))


def _block_totals(b: _Block, q: Query, rng: np.random.Generator,
                  n_keys: int, bound: bool):
    """(count, clipped sum, units) per key of one block's release: every
    unit keeps a uniform sample of l0 of its pairs and each pair a
    uniform sample of linf of its rows, or everything without bounding."""
    row_pair = np.repeat(np.arange(len(b.n)), b.n)
    keep_row = np.ones(len(row_pair), dtype=bool)
    keep_pair = np.ones(len(b.n), dtype=bool)
    if bound:
        unit = np.cumsum(b.unit_first) - 1
        order = np.lexsort((rng.random(len(unit)), unit))
        first = np.flatnonzero(b.unit_first)
        keep_pair[order] = (np.arange(len(unit)) - first[unit[order]]
                            < q.l0)
        order = np.lexsort((rng.random(len(row_pair)), row_pair))
        rank = np.arange(len(row_pair)) - b.starts[row_pair[order]]
        keep_row[order] = rank < q.linf
        keep_row &= keep_pair[row_pair]
    row_key = b.pk[row_pair[keep_row]]
    v = np.clip(b.value[keep_row], q.lo, q.hi)
    return (np.bincount(row_key, minlength=n_keys).astype(np.float64),
            np.bincount(row_key, weights=v, minlength=n_keys),
            np.bincount(b.pk[keep_pair], minlength=n_keys))


def release(pairs: Pairs, q: Query, rng: np.random.Generator, *,
            bound: bool = True) -> Release:
    """The plain release: bound by uniform sampling, aggregate, select,
    noise, all in float64 numpy. ``bound=False`` is the control."""
    streams = rng.spawn(len(pairs.blocks))
    count, total, units = _parallel(
        lambda i: _block_totals(pairs.blocks[i], q, streams[i],
                                pairs.n_keys, bound),
        range(len(pairs.blocks)),
        lambda a, b: tuple(x + y for x, y in zip(a, b)))
    pi = keep_probability(q, int(units.max()) + 1)
    kept = pairs.present & (rng.random(pairs.n_keys) < pi[units])
    keys = np.flatnonzero(kept)
    sd = noise_sd(q)
    exact = {"COUNT": count, "SUM": total,
             "PRIVACY_ID_COUNT": units.astype(np.float64)}
    values = {}
    for m in q.metrics:
        if q.noise_kind == "LAPLACE":
            noise = rng.laplace(0.0, sd[m] / math.sqrt(2.0), len(keys))
        else:
            noise = rng.normal(0.0, sd[m], len(keys))
        values[m] = exact[m][keys] + noise
    return Release(keys=keys, values=values)
