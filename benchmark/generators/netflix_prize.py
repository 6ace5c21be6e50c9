"""Rows shaped like the Netflix Prize training set.

The published totals are kept exactly: ``n_rows`` ratings of
``n_partitions`` movies (ids 1..M) by ``n_users`` customers, each
(customer, movie) pair at most once, integer stars 1..5. What the
dataset's summary does not fix is assumed (the config's ``assumed``):

* rows per customer follow a log-normal law with the published median,
  floored at 1 and capped at the published maximum, its spread solved so
  that the mean is n_rows / n_users; the profile is the law's quantiles;
* movie popularity follows a log-normal law with the published median,
  minimum and maximum in the same way (mean n_rows / n_partitions);
  movie ids are assigned to popularity ranks at random;
* each customer's movies are drawn from the popularity law without
  replacement, by mapping sorted uniforms through the popularity CDF
  (movies in order of popularity) and moving a repeated draw to the next
  free rank, the next most popular movie not yet drawn; a movie that no
  draw reached takes one row of the most rated movie, so all M movies
  appear;
* customer ids are drawn without replacement from the published id
  range; ids and row counts are the same under every seed, which draws
  the movies, the ratings and the popularity ranks;
* ratings follow the configured marginals;
* rows are ordered by movie, as in the dataset's per-movie files, and
  by customer within a movie.

The customers are cut into ``BLOCKS`` fixed blocks, each with its own
random stream, that threads fill side by side: the rows depend on the
seed alone, not on the number of threads.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np
from scipy import special

BLOCKS = 32
# The customers' ids and row counts come from this fixed seed, not from
# --seed: the engine sizes its chunks from the rows per privacy unit, so
# every seed then runs the same compiled programs.
PROFILE_SEED = 0


def _capped_lognormal(n: int, median: float, mean: float, lo: float,
                      hi: float) -> np.ndarray:
    """The n quantiles of a log-normal law with this median, clipped to
    [lo, hi], its spread solved so that the clipped mean is ``mean``."""
    z = special.ndtri((np.arange(n) + 0.5) / n)

    def clipped(sigma):
        return np.clip(median * np.exp(sigma * z), lo, hi)

    a, b = 1e-3, 8.0
    for _ in range(100):
        mid = 0.5 * (a + b)
        if clipped(mid).mean() < mean:
            a = mid
        else:
            b = mid
    return clipped(0.5 * (a + b))


def _integer_profile(profile: np.ndarray, total: int, hi: int) -> np.ndarray:
    """Integers in [1, hi] proportional to ``profile`` that sum to
    ``total`` (largest remainders)."""
    scaled = profile * (total / profile.sum())
    out = np.clip(np.floor(scaled), 1, hi).astype(np.int64)
    short = total - int(out.sum())
    frac = np.where(out < hi, scaled - np.floor(scaled), -1.0)
    if short > 0:
        out[np.argsort(-frac, kind="stable")[:short]] += 1
    elif short < 0:
        out[np.argsort(np.where(out > 1, frac, 2.0),
                       kind="stable")[:-short]] -= 1
    if int(out.sum()) != total or out.max() > hi or out.min() < 1:
        raise ValueError("cannot fit the activity profile to the totals")
    return out


def _draw_movies(deg: np.ndarray, cdf: np.ndarray, movie_of_rank,
                 rng: np.random.Generator) -> np.ndarray:
    """Distinct movies for customers with these row counts, in customer
    order: sorted uniforms from exponential spacings (deg[u] + 1 per
    customer) through the popularity CDF, repeats moved to the next free
    rank."""
    m = len(cdf)
    users = len(deg)
    n = int(deg.sum())
    start = np.zeros(users, dtype=np.int64)
    np.cumsum(deg[:-1], out=start[1:])
    seg = np.repeat(np.arange(users, dtype=np.int64), deg)
    rank = np.arange(n, dtype=np.int64) - start[seg]
    spacing = rng.standard_exponential(n + users)
    np.cumsum(spacing, out=spacing)
    first = start + np.arange(users)
    before = np.zeros(users)
    before[1:] = spacing[first[1:] - 1]
    total = spacing[first + deg] - before
    u = spacing[first[seg] + rank]
    del spacing
    u -= before[seg]
    u /= total[seg]
    idx = np.searchsorted(cdf, u, side="right")
    del u
    np.minimum(idx, m - 1, out=idx)
    # Within a customer the sorted ranks become strictly increasing and
    # stay below m: rank_r = r + min(max_{j<=r}(idx_j - j), m - deg).
    width = 1 << (int(max(m, int(deg.max()))).bit_length() + 1)
    key = idx - rank + width + seg * (2 * width)
    np.maximum.accumulate(key, out=key)
    key -= seg * (2 * width) + width
    np.minimum(key, (m - deg)[seg], out=key)
    key += rank
    return movie_of_rank[key]


def make_columns(cfg: dict, seed: int, threads: int = 0):
    """(pid int32, pk int32, value float32) for ``cfg["data"]``."""
    d = cfg["data"]
    n, m, users = d["n_rows"], d["n_partitions"], d["n_users"]
    act, pop = d["user_rows"], d["movie_rows"]
    streams = np.random.SeedSequence(seed).spawn(BLOCKS + 1)
    rng = np.random.default_rng(streams[0])

    profile = np.random.default_rng(PROFILE_SEED)
    deg = _integer_profile(
        _capped_lognormal(users, act["median"], n / users, 1, act["max"]),
        n, min(act["max"], m))[profile.permutation(users)]
    uid = (profile.choice(d["user_id_max"], users, replace=False)
           + 1).astype(np.int32)
    weight = _capped_lognormal(m, pop["median"], n / m, pop["min"],
                               pop["max"])[::-1]
    movie_of_rank = rng.permutation(m).astype(np.int32)
    cdf = np.cumsum(weight)
    cdf /= cdf[-1]
    marg = np.cumsum(d["rating_marginals"])
    marg /= marg[-1]

    user_cut = np.linspace(0, users, BLOCKS + 1).astype(np.int64)
    row_cut = np.zeros(BLOCKS + 1, dtype=np.int64)
    row_cut[1:] = np.cumsum(deg)[user_cut[1:] - 1]
    movie = np.empty(n, dtype=np.int32)
    stars = np.empty(n, dtype=np.int8)

    def fill(b):
        r = np.random.default_rng(streams[b + 1])
        lo, hi = row_cut[b], row_cut[b + 1]
        movie[lo:hi] = _draw_movies(deg[user_cut[b]:user_cut[b + 1]], cdf,
                                    movie_of_rank, r)
        stars[lo:hi] = np.searchsorted(marg, r.random(hi - lo),
                                       side="right") + 1

    with concurrent.futures.ThreadPoolExecutor(
            threads or min(BLOCKS, os.cpu_count() or 1)) as pool:
        list(pool.map(fill, range(BLOCKS)))

    count = np.bincount(movie, minlength=m)
    missing = np.flatnonzero(count == 0)
    if len(missing):
        donor = np.flatnonzero(movie == int(np.argmax(count)))
        movie[donor[:len(missing)]] = missing

    # Stable counting placement by movie, block by block: rows of movie
    # j from block b land after those of blocks < b.
    per_block = np.stack([
        np.bincount(movie[row_cut[b]:row_cut[b + 1]], minlength=m)
        for b in range(BLOCKS)])
    offset = np.zeros((m, BLOCKS), dtype=np.int64)
    flat = per_block.T.ravel()
    offset.ravel()[1:] = np.cumsum(flat)[:-1]
    seg_user = np.repeat(uid, deg)
    pid = np.empty(n, dtype=np.int32)
    pk = np.empty(n, dtype=np.int32)
    value = np.empty(n, dtype=np.float32)

    def place(b):
        lo, hi = row_cut[b], row_cut[b + 1]
        mv = movie[lo:hi]
        order = np.argsort(mv.astype(np.int16 if m < 2**15 else np.int32),
                           kind="stable")
        sm = mv[order]
        local = np.zeros(m, dtype=np.int64)
        local[1:] = np.cumsum(per_block[b])[:-1]
        dest = offset[sm, b] + np.arange(hi - lo) - local[sm]
        pid[dest] = seg_user[lo:hi][order]
        pk[dest] = sm + 1
        value[dest] = stars[lo:hi][order]

    with concurrent.futures.ThreadPoolExecutor(
            threads or min(BLOCKS, os.cpu_count() or 1)) as pool:
        list(pool.map(place, range(BLOCKS)))
    return pid, pk, value
