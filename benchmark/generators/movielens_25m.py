"""Rows shaped like MovieLens 25M (``ml-25m/ratings.csv``).

The published totals are kept exactly: ``n_rows`` ratings by ``n_users``
users (ids 1..n_users), each of whom rated at least ``user_rows.min``
movies, of ``n_partitions`` rated movies, each (user, movie) pair at most
once, half-star values 0.5..5.0. What the README does not fix is assumed
(the config's ``assumed``):

* rows per user follow a log-normal law with the assumed median, clipped
  to [min, max] with its spread solved so that the mean is n_rows /
  n_users; the heaviest user has exactly the published maximum; the
  profile is the law's quantiles, made integers that sum to n_rows;
* movie popularity follows a log-normal law in the same way (at least
  one rating, the most rated movie at the published maximum), made
  integers that sum to n_rows; the pairs are drawn from it as in
  ``netflix_prize`` (its helpers, imported): each user's movies without
  replacement, a repeated draw moved to the next most popular movie not
  yet drawn; a movie that no draw reached takes one row of one of the
  most rated movies, so all ``n_partitions`` movies appear;
* movie ids are ``n_partitions`` distinct ids of the published range
  1..movie_id_max, assigned to popularity ranks at random;
* values follow the configured half-star marginals;
* rows are ordered by user, then by movie id, as in ``ratings.csv``.

User ids, row counts and the movie id set are the same under every
seed (the engine sizes its chunks from the rows per privacy unit); the
seed draws the movies, the values and the popularity ranks. The users
are cut into ``netflix_prize.BLOCKS`` fixed blocks, each with its own
random stream, so the rows depend on the seed alone, not on the number
of threads.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

from benchmark.generators import netflix_prize as nf

BLOCKS = nf.BLOCKS
PROFILE_SEED = nf.PROFILE_SEED
MOVIE_ID_BITS = 18  # movie ids < 2**18 in the sort key of the row order


def _profile(n: int, total: int, median: float, lo: int, hi: int):
    """n integers in [lo, hi] that sum to ``total``, ascending, the last
    exactly ``hi``: the quantiles of a log-normal law with this median,
    clipped to [lo, hi], its spread solved for the mean total / n."""
    law = nf._capped_lognormal(n, median, total / n, lo, hi)
    # Shifted by lo - 1 so that the integer fit's floor of 1 is lo.
    rest = nf._integer_profile(law[:-1] - (lo - 1),
                               total - hi - (lo - 1) * (n - 1),
                               hi - (lo - 1)) + (lo - 1)
    return np.append(np.sort(rest), hi)


def profiles(cfg: dict):
    """(rows per user, rows per movie): integer profiles, each summing to
    ``n_rows``, ascending. No row is drawn."""
    d = cfg["data"]
    n = d["n_rows"]
    u, m = d["user_rows"], d["movie_rows"]
    if u["max"] > d["n_partitions"] or m["max"] > d["n_users"]:
        raise ValueError("a user rates each movie at most once")
    if d["movie_id_max"] >> MOVIE_ID_BITS:
        raise ValueError(f"movie ids must stay below 2**{MOVIE_ID_BITS}")
    users = _profile(d["n_users"], n, u["median"], u["min"], u["max"])
    movies = _profile(d["n_partitions"], n, m["median"], m["min"], m["max"])
    return users, movies


def make_columns(cfg: dict, seed: int, threads: int = 0):
    """(pid int32, pk int32, value float32) for ``cfg["data"]``."""
    d = cfg["data"]
    n, m, users = d["n_rows"], d["n_partitions"], d["n_users"]
    user_rows, movie_rows = profiles(cfg)
    streams = np.random.SeedSequence(seed).spawn(BLOCKS + 1)
    rng = np.random.default_rng(streams[0])

    profile = np.random.default_rng(PROFILE_SEED)
    deg = user_rows[profile.permutation(users)]
    movie_ids = np.sort(profile.choice(d["movie_id_max"], m, replace=False)
                        + 1).astype(np.int32)
    movie_of_rank = movie_ids[rng.permutation(m)]
    cdf = np.cumsum(movie_rows[::-1], dtype=np.float64)
    cdf /= cdf[-1]
    marg = np.cumsum(d["rating_marginals"], dtype=np.float64)
    marg /= marg[-1]
    stars = np.asarray(d["rating_values"], dtype=np.float32)

    user_cut = np.linspace(0, users, BLOCKS + 1).astype(np.int64)
    row_cut = np.zeros(BLOCKS + 1, dtype=np.int64)
    row_cut[1:] = np.cumsum(deg)[user_cut[1:] - 1]
    movie = np.empty(n, dtype=np.int32)
    level = np.empty(n, dtype=np.int8)

    def fill(b):
        r = np.random.default_rng(streams[b + 1])
        lo, hi = row_cut[b], row_cut[b + 1]
        movie[lo:hi] = nf._draw_movies(deg[user_cut[b]:user_cut[b + 1]],
                                       cdf, movie_of_rank, r)
        level[lo:hi] = np.searchsorted(marg, r.random(hi - lo),
                                       side="right")

    width = threads or min(BLOCKS, os.cpu_count() or 1)
    with concurrent.futures.ThreadPoolExecutor(width) as pool:
        list(pool.map(fill, range(BLOCKS)))

    # Movies no draw reached: one row each from the most rated movies,
    # one donor movie per missing movie (a missing movie has no rows, so
    # no pair repeats).
    slot_of = np.zeros(d["movie_id_max"] + 1, dtype=np.int32)
    slot_of[movie_ids] = np.arange(m, dtype=np.int32)
    slot = slot_of[movie]
    count = np.bincount(slot, minlength=m)
    missing = np.flatnonzero(count == 0)
    if len(missing):
        donors = np.argsort(-count, kind="stable")[:len(missing)]
        is_donor = np.zeros(m, dtype=bool)
        is_donor[donors] = True
        rows = np.flatnonzero(is_donor[slot])
        _, first = np.unique(slot[rows], return_index=True)
        movie[rows[first]] = movie_ids[missing]
    del slot

    pid = np.empty(n, dtype=np.int32)
    pk = np.empty(n, dtype=np.int32)
    value = np.empty(n, dtype=np.float32)
    uid = np.arange(1, users + 1, dtype=np.int64)

    def place(b):
        lo, hi = row_cut[b], row_cut[b + 1]
        owner = np.repeat(uid[user_cut[b]:user_cut[b + 1]],
                          deg[user_cut[b]:user_cut[b + 1]])
        order = np.argsort((owner << MOVIE_ID_BITS) | movie[lo:hi])
        pid[lo:hi] = owner[order]
        pk[lo:hi] = movie[lo:hi][order]
        value[lo:hi] = stars[level[lo:hi][order]]

    with concurrent.futures.ThreadPoolExecutor(width) as pool:
        list(pool.map(place, range(BLOCKS)))
    return pid, pk, value
