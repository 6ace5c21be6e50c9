"""Host-side dictionary encoding of arbitrary keys to dense int32 ids.

The columnar engine works on fixed-shape integer arrays; arbitrary privacy
ids and partition keys (strings, tuples, ...) are encoded on host to dense
ids (SURVEY.md §7 "String keys"). Public-partition filtering becomes a
vocabulary-membership test during encoding, so non-public rows never reach
the device.

Three input shapes, fastest first:
  * EncodedColumns — ids already dense int32: zero host work.
  * ColumnarData — raw numpy columns: vectorized np.unique factorization.
  * Python rows + extractors — per-row extraction, vectorized encoding of
    the extracted columns.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from pipelinedp_tpu.obs import trace as obs_trace


class Vocabulary:
    """Bidirectional key <-> dense id mapping.

    Vocabularies built from a distinct-keys array (`from_unique`) stay as
    that array; the Python dict for reverse lookup is materialized only if
    `lookup`/`add` is actually called — encoding a 100M-row dataset must
    not pay for a multi-million-entry dict it never reads.
    """

    def __init__(self, keys: Optional[Sequence[Any]] = None):
        self._key_to_id: Optional[Dict[Any, int]] = {}
        self._keys: List[Any] = []
        self._unique_arr: Optional[np.ndarray] = None
        if keys is not None:
            for key in keys:
                self.add(key)

    @classmethod
    def from_unique(cls, unique_keys: np.ndarray) -> "Vocabulary":
        """Wraps an array of distinct keys; id i maps to unique_keys[i]."""
        vocab = cls()
        vocab._unique_arr = np.asarray(unique_keys)
        vocab._key_to_id = None  # built lazily
        return vocab

    def _materialize(self) -> None:
        if self._unique_arr is not None:
            self._keys = [k.item() if hasattr(k, "item") else k
                          for k in self._unique_arr]
            self._unique_arr = None
        if self._key_to_id is None:
            self._key_to_id = {k: i for i, k in enumerate(self._keys)}

    def add(self, key: Any) -> int:
        self._materialize()
        idx = self._key_to_id.get(key)
        if idx is None:
            idx = len(self._keys)
            self._key_to_id[key] = idx
            self._keys.append(key)
        return idx

    def lookup(self, key: Any) -> int:
        """Returns the id or -1 if unknown."""
        self._materialize()
        return self._key_to_id.get(key, -1)

    def decode(self, idx: int) -> Any:
        if self._unique_arr is not None:
            key = self._unique_arr[idx]
            return key.item() if hasattr(key, "item") else key
        return self._keys[idx]

    def decode_all(self, ids: Sequence[int]) -> List[Any]:
        if self._unique_arr is not None:
            picked = self._unique_arr[np.asarray(ids, dtype=np.int64)]
            return [k.item() if hasattr(k, "item") else k for k in picked]
        return [self._keys[i] for i in ids]

    @property
    def keys(self) -> List[Any]:
        if self._unique_arr is not None:
            return [k.item() if hasattr(k, "item") else k
                    for k in self._unique_arr]
        return list(self._keys)

    def __len__(self) -> int:
        if self._unique_arr is not None:
            return len(self._unique_arr)
        return len(self._keys)


@dataclasses.dataclass
class ColumnarData:
    """Raw columnar input: one entry per contribution.

    ``pid``/``pk`` may be any numpy-comparable dtype (ints, strings, ...);
    they are factorized to dense ids with vectorized np.unique. ``value``
    may be None (COUNT-style metrics), float[N], or float[N, D] for
    VECTOR_SUM.
    """
    pid: np.ndarray
    pk: np.ndarray
    value: Optional[np.ndarray] = None


@dataclasses.dataclass
class EncodedColumns:
    """Pre-encoded columnar input: ids are already dense int32.

    ``pid`` in [0, num_privacy_units), ``pk`` in [0, num_partitions). The
    partition vocabulary maps ids back to user-facing keys; identity if
    omitted. This is the zero-host-cost path for data that already lives
    in dense-id form (e.g. the output of a previous pipeline stage).
    """
    pid: np.ndarray
    pk: np.ndarray
    num_partitions: int
    value: Optional[np.ndarray] = None
    pk_keys: Optional[Sequence[Any]] = None  # id -> key, identity if None


_SCALAR_KEY_TYPES = (int, float, str, bytes, bool, np.generic)


def _column_from_list(values: List[Any]) -> np.ndarray:
    """Column array from extracted keys, preserving composite keys.

    np.asarray would splat tuple keys into a 2-D array and coerce mixed
    int/str keys to strings; keys must stay whole, so anything that is not
    uniformly a scalar type becomes a 1-D object array.
    """
    types = {type(v) for v in values}
    if len(types) == 1 and issubclass(next(iter(types)), _SCALAR_KEY_TYPES):
        return np.asarray(values)
    arr = np.empty(len(values), dtype=object)
    arr[:] = values
    return arr


def _factorize(column: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(dense int32 ids, unique keys). Vectorized for non-object dtypes."""
    column = np.asarray(column)
    if column.dtype == object:
        # Mixed/unhashable-by-numpy keys: dict-based single pass.
        vocab: Dict[Any, int] = {}
        ids = np.empty(len(column), dtype=np.int32)
        for i, key in enumerate(column):
            idx = vocab.setdefault(key, len(vocab))
            ids[i] = idx
        uniques = np.empty(len(vocab), dtype=object)
        for key, idx in vocab.items():
            uniques[idx] = key
        return ids, uniques
    if np.issubdtype(column.dtype, np.integer) and len(column):
        lo = int(column.min())
        hi = int(column.max())
        span = hi - lo + 1
        # Presence-table factorization: O(N + span) beats the O(N log N)
        # sort when the id range is not much larger than the data.
        if 0 < span <= max(4 * len(column), 1 << 20):
            shifted = column - lo if lo else column
            present = np.zeros(span, dtype=bool)
            present[shifted] = True
            if present.all():
                # Ids are already dense on [lo, hi]: identity mapping, no
                # remap gather (2 s saved at the 100M-row benchmark shape).
                # The ids array must be a FRESH buffer: results are
                # computed lazily, so aliasing the caller's column would
                # let a later caller-side mutation corrupt the encoded
                # ids (shifted aliases `column` when lo == 0).
                ids = (shifted.astype(np.int32, copy=True)
                       if shifted is column else
                       shifted.astype(np.int32, copy=False))
                return ids, np.arange(lo, hi + 1, dtype=column.dtype)
            ids_map = np.cumsum(present, dtype=np.int32) - 1
            ids = ids_map[shifted]
            uniques = np.flatnonzero(present) + lo
            return ids, uniques.astype(column.dtype)
    uniques, inverse = np.unique(column, return_inverse=True)
    return inverse.astype(np.int32), uniques


def _lookup_ids(column: np.ndarray, vocab: Vocabulary) -> np.ndarray:
    """ids of column entries under an existing vocabulary (-1 = unknown),
    vectorized via sorted search against the vocabulary keys."""
    column = np.asarray(column)
    if column.dtype == object or len(vocab) == 0:
        return np.fromiter((vocab.lookup(k) for k in column),
                           dtype=np.int32,
                           count=len(column))
    keys = np.asarray(vocab.keys)
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    pos = np.searchsorted(sorted_keys, column)
    pos = np.clip(pos, 0, len(keys) - 1)
    found = sorted_keys[pos] == column
    ids = np.where(found, order[pos], -1)
    return ids.astype(np.int32)


_INT32_SENTINEL_SAFE = np.iinfo(np.int32).max - 1


def _pid_passthrough(pid_col: np.ndarray) -> Optional[np.ndarray]:
    """Raw integer privacy ids shifted to [0, span], or None if unusable.

    The kernels only compare privacy ids for equality, so dense
    factorization is pure overhead when the input ids are already integers
    — a shift-to-zero keeps them inside int32 (the kernel reserves
    INT32_MAX as its padding sentinel, hence the safety margin).

    Read-only contract: when the input is already int32 with lo == 0 the
    returned array ALIASES the caller's column (this is the hot path; a
    defensive copy would cost ~0.2 s at the 100M-row shape). Engine call
    sites treat encoded pid columns as immutable.
    """
    if not np.issubdtype(pid_col.dtype, np.integer) or len(pid_col) == 0:
        return None
    lo = int(pid_col.min())
    span = int(pid_col.max()) - lo
    if span >= _INT32_SENTINEL_SAFE:
        return None
    shifted = pid_col - lo if lo else pid_col
    return shifted.astype(np.int32, copy=False)


def encode_columns(
    pid_col,
    pk_col,
    value_col,
    public_partitions: Optional[Sequence[Any]] = None,
    vector_size: Optional[int] = None,
    factorize_pid: bool = True,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Optional[Vocabulary],
           Vocabulary]:
    """Vectorized encoding of raw columns; same contract as encode_rows.

    ``pid_col`` may be None (contribution_bounds_already_enforced: each row
    becomes its own privacy unit). With ``factorize_pid=False`` integer
    privacy ids skip factorization entirely (returned shifted-to-zero with
    pid_vocab=None) — the kernels never need dense pid ids, only equality.

    One child span per column pass of the engine's ``dp/encode``:
    ``dp/encode/pk``, ``dp/encode/pid`` and ``dp/encode/value``.
    """
    pk_col = np.asarray(pk_col)
    if pid_col is not None:
        pid_col = np.asarray(pid_col)
    with obs_trace.span("dp/encode/pk", public=public_partitions is not None):
        if public_partitions is not None:
            pk_vocab = Vocabulary(public_partitions)
            pk_ids = _lookup_ids(pk_col, pk_vocab)
            keep = pk_ids >= 0
            pk_ids = pk_ids[keep]
            if pid_col is not None:
                pid_col = pid_col[keep]
            if value_col is not None:
                value_col = np.asarray(value_col)[keep]
        else:
            pk_ids, pk_uniques = _factorize(pk_col)
            pk_vocab = Vocabulary.from_unique(pk_uniques)
    with obs_trace.span("dp/encode/pid"):
        if pid_col is None:
            pid_ids = np.arange(len(pk_ids), dtype=np.int32)
            pid_vocab = Vocabulary.from_unique(np.arange(len(pk_ids)))
        else:
            pid_ids = None if factorize_pid else _pid_passthrough(pid_col)
            if pid_ids is not None:
                pid_vocab = None
            else:
                pid_ids, pid_uniques = _factorize(pid_col)
                pid_vocab = Vocabulary.from_unique(pid_uniques)
        pid_ids = pid_ids.astype(np.int32, copy=False)
    with obs_trace.span("dp/encode/value"):
        value_arr = _value_array(value_col, len(pk_ids), vector_size)
    return (pid_ids, pk_ids.astype(np.int32, copy=False), value_arr,
            pid_vocab, pk_vocab)


def _value_array(value_col, n: int,
                 vector_size: Optional[int]) -> np.ndarray:
    if value_col is None:
        return np.zeros(n, dtype=np.float32)
    arr = np.asarray(value_col, dtype=np.float32)
    if vector_size is not None:
        return arr.reshape(n, vector_size)
    return arr


def encode_rows(
    rows,
    privacy_id_extractor,
    partition_extractor,
    value_extractor,
    public_partitions: Optional[Sequence[Any]] = None,
    vector_size: Optional[int] = None,
    factorize_pid: bool = True,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Optional[Vocabulary],
           Vocabulary]:
    """Encodes Python rows into (pid_ids, pk_ids, values) numpy columns.

    Columnar inputs (ColumnarData / EncodedColumns) skip the per-row
    extractor loop entirely. With ``public_partitions`` the partition
    vocabulary is frozen up front and rows with non-public partitions are
    dropped (the public-path filter_by_key of the reference graph,
    dp_engine.py:290).
    """
    if isinstance(rows, EncodedColumns):
        return _encode_pre_encoded(rows, public_partitions, vector_size,
                                   use_pid=privacy_id_extractor is not None)
    if isinstance(rows, ColumnarData):
        pid_col = rows.pid if privacy_id_extractor is not None else None
        return encode_columns(pid_col, rows.pk, rows.value,
                              public_partitions, vector_size,
                              factorize_pid=factorize_pid)
    rows = list(rows)
    pk_col = _column_from_list([partition_extractor(row) for row in rows])
    if privacy_id_extractor is not None and privacy_id_extractor is not True:
        pid_col = _column_from_list(
            [privacy_id_extractor(row) for row in rows])
    else:
        pid_col = None
    if value_extractor is not None:
        value_col = [value_extractor(row) for row in rows]
    else:
        value_col = None
    return encode_columns(pid_col, pk_col, value_col, public_partitions,
                          vector_size, factorize_pid=factorize_pid)


def _encode_pre_encoded(cols: EncodedColumns,
                        public_partitions: Optional[Sequence[Any]],
                        vector_size: Optional[int],
                        use_pid: bool = True):
    pid = np.asarray(cols.pid, dtype=np.int32)
    pk = np.asarray(cols.pk, dtype=np.int32)
    if not use_pid:
        # contribution_bounds_already_enforced: each row is its own unit.
        pid = np.arange(len(pk), dtype=np.int32)
    pk_keys = (cols.pk_keys
               if cols.pk_keys is not None else range(cols.num_partitions))
    pk_vocab = Vocabulary.from_unique(np.asarray(pk_keys))
    if len(pk_vocab) != cols.num_partitions:
        raise ValueError(
            f"pk_keys has {len(pk_vocab)} entries, expected "
            f"num_partitions={cols.num_partitions}")
    value = cols.value
    if public_partitions is not None:
        # Re-encode against a public-only vocabulary: non-public ids must
        # not survive into the output partition space.
        public_vocab = Vocabulary(public_partitions)
        table = np.full(cols.num_partitions, -1, dtype=np.int32)
        for new_id, key in enumerate(public_vocab.keys):
            old_id = pk_vocab.lookup(key)
            if old_id >= 0:
                table[old_id] = new_id
        pk = table[pk]
        mask = pk >= 0
        pid, pk = pid[mask], pk[mask]
        if value is not None:
            value = np.asarray(value)[mask]
        pk_vocab = public_vocab
    # Privacy-id vocabulary is identity over the observed id space.
    n_pids = int(pid.max()) + 1 if len(pid) else 0
    pid_vocab = Vocabulary.from_unique(np.arange(n_pids))
    return (pid, pk, _value_array(value, len(pk), vector_size), pid_vocab,
            pk_vocab)
