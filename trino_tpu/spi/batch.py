"""Columnar batch data model (the Page/Block equivalent).

Mirrors Trino's ``io.trino.spi.Page`` / ``spi/block/Block`` (reference:
core/trino-spi/src/main/java/io/trino/spi/Page.java:95, spi/block/Block.java:23)
re-designed for XLA:

- A :class:`Column` is one fixed-shape 1-D array (``numpy`` on host, moved to
  device at kernel boundaries) + an optional validity mask (True = non-null).
  This replaces the four sealed Block shapes (ValueBlock / DictionaryBlock /
  RunLengthEncodedBlock / LazyBlock): dictionary encoding is *mandatory* for
  strings, RLE is left to XLA's fusion, and laziness lives in the connector
  (columns are only generated/loaded when the plan projects them).
- String columns store ``int32`` codes into a host-side **sorted** dictionary
  (``np.ndarray`` of python str).  Sortedness makes code-space comparisons
  order-correct, so <, >, ORDER BY, MIN/MAX run on the device on codes alone.
  String *functions* are dictionary transforms evaluated host-side over the
  (small) dictionary, then a device-side gather remaps codes — the TPU never
  touches bytes of text.
- A :class:`ColumnBatch` is an ordered set of equal-length Columns, the unit
  that flows between operators (Trino targets ~1MB Pages; we target fixed
  row-count batches so jit caches hit).
"""

from __future__ import annotations

import datetime
import decimal
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from .types import (
    BOOLEAN,
    DATE,
    DOUBLE,
    TIMESTAMP,
    ArrayType,
    DecimalType,
    MapType,
    RowType,
    Type,
    days_to_date,
)

__all__ = ["Column", "ColumnBatch", "encode_strings", "unify_dictionaries",
           "round_up_pow2", "pad_to_bucket", "encoded_exec", "maybe_rle",
           "set_materialize_hook"]


def encoded_exec() -> bool:
    """Compressed execution master switch (TRINO_TPU_ENCODED_EXEC):
    ``auto``/``1`` let operators consume RLE/LAZY/dictionary encodings
    directly; ``0`` is the bit-for-bit legacy expand-at-scan path."""
    import os

    return os.environ.get("TRINO_TPU_ENCODED_EXEC", "auto") != "0"


# telemetry hook (set by telemetry/metrics.py): called with
# (encoding, nbytes) whenever an encoded column materializes its flat
# representation.  A plain module global so spi stays import-light.
_MATERIALIZE_HOOK = None


def set_materialize_hook(fn) -> None:
    global _MATERIALIZE_HOOK
    _MATERIALIZE_HOOK = fn


def round_up_pow2(n: int, minimum: int = 8) -> int:
    """Round up to a power of two — the static-shape recompile bucket.  All
    batch shapes in the jitted data plane are bucketed so XLA programs are
    compiled once per (pipeline, bucket) instead of once per row count."""
    c = minimum
    while c < n:
        c <<= 1
    return c


def encode_strings(values: Sequence[str | None]) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Encode python strings into (codes, valid, sorted_dictionary)."""
    valid = np.array([v is not None for v in values], dtype=np.bool_)
    filled = np.array([v if v is not None else "" for v in values], dtype=object)
    dictionary, codes = np.unique(filled, return_inverse=True)
    return codes.astype(np.int32), valid, dictionary


def _canon_key(v):
    """Deterministic sort key for array dictionary entries: lexicographic
    with NULL elements last (comparisons must never hit None<x)."""
    return tuple((e is None, e if e is not None else 0) for e in v)


def _object_array(values) -> np.ndarray:
    # np.array(list_of_equal_len_tuples) would build a 2-D array; fill by slot
    out = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        out[i] = v
    return out


def encode_arrays(values: Sequence) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Encode python sequences (arrays) into (codes, valid, dictionary of
    tuples).  Same contract as encode_strings, tuple-valued dictionary."""
    filled = [tuple(v) if v is not None else () for v in values]
    valid = np.array([v is not None for v in values], dtype=np.bool_)
    uniq = sorted(set(filled), key=_canon_key)
    pos = {v: i for i, v in enumerate(uniq)}
    codes = np.array([pos[v] for v in filled], dtype=np.int32)
    return codes, valid, _object_array(uniq)


def encode_sorted_objects(values: Sequence, null_fill
                          ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Encode naturally-orderable python objects (long-decimal scaled ints)
    into (codes, valid, sorted dictionary)."""
    valid = np.array([v is not None for v in values], dtype=np.bool_)
    filled = [v if v is not None else null_fill for v in values]
    uniq = sorted(set(filled))
    pos = {v: i for i, v in enumerate(uniq)}
    codes = np.array([pos[v] for v in filled], dtype=np.int32)
    return codes, valid, _object_array(uniq)


# dictionary byte accounting: object-dtype dictionaries (strings, tuples)
# report pointer bytes via .nbytes, so the real payload is summed once and
# memoized by (id, len) — accounting, not an exact allocator figure
_DICT_NBYTES_CACHE: dict = {}


def _dictionary_nbytes(d) -> int:
    if d is None:
        return 0
    if d.dtype != object:
        return int(d.nbytes)
    key = id(d)
    hit = _DICT_NBYTES_CACHE.get(key)
    if hit is not None and hit[0] == len(d):
        return hit[1]
    total = 0
    for v in d:
        total += len(str(v).encode("utf-8", "replace"))
    if len(_DICT_NBYTES_CACHE) > 4096:
        _DICT_NBYTES_CACHE.clear()
    _DICT_NBYTES_CACHE[key] = (len(d), total)
    return total


class Column:
    """One column of a batch: fixed-width array + validity + dictionary.

    ``data``/``valid`` may be numpy (host) OR jax arrays (device-resident):
    the engine's hot path keeps columns on device between operators and only
    materializes to host at true boundaries (exchange serialization, client
    results, oracle diffs).  Mirrors how the reference keeps Pages inside the
    JVM heap between compiled operators (operator/Driver.java:403-408).

    The reference's sealed Block shapes are carried as an ``encoding`` tag
    instead of subclasses (spi/block/Block.java:23):

    - ``FLAT``  — dense array (ValueBlock)
    - ``DICT``  — FLAT int32 codes + a host-side sorted ``dictionary``
      (DictionaryBlock; mandatory for strings)
    - ``RLE``   — ONE stored value + a run length (RunLengthEncodedBlock);
      ``valid`` may still be a full-length mask (nulls inside the run)
    - ``LAZY``  — a thunk producing ``(data, valid)`` on first touch
      (LazyBlock); until touched the column costs no HBM and no PCIe

    Touching ``.data``/``.valid`` on an encoded column materializes the
    flat view exactly once (RLE materializes as a zero-copy broadcast
    view).  Encoding-aware operators check ``.encoding`` first and never
    touch the flat view on their fast paths."""

    __slots__ = ("type", "dictionary", "_data", "_valid", "_length",
                 "_enc", "_rle_value", "_thunk", "_nbytes_hint", "_derived")

    def __init__(self, type: Type, data, valid=None, dictionary=None):
        self.type = type
        self.dictionary = dictionary
        self._enc = "FLAT"
        self._rle_value = None
        self._thunk = None
        self._nbytes_hint = 0
        self._derived = False
        self._data = data
        self._length = int(data.shape[0])
        self._valid = valid
        self.__post_init__()

    def __post_init__(self):
        # normalizing all-valid masks to None requires a host sync for device
        # arrays — only do it for numpy
        if isinstance(self._valid, np.ndarray) and self._valid.all():
            self._valid = None

    # -- encoded constructors ------------------------------------------------

    @staticmethod
    def rle(type_: Type, value, length: int, valid=None,
            dictionary=None) -> "Column":
        """Run-length column: one stored value repeated ``length`` times.
        ``value`` is the storage-dtype scalar (the int32 code for
        dictionary columns); ``valid`` may be a full-length mask so a run
        can contain NULLs without breaking the encoding."""
        c = Column.__new__(Column)
        c.type = type_
        c.dictionary = dictionary
        c._enc = "RLE"
        dtype = np.int32 if dictionary is not None else type_.storage_dtype
        c._rle_value = np.asarray(value, dtype=dtype)
        c._thunk = None
        c._nbytes_hint = 0
        c._derived = False
        c._data = None
        c._length = int(length)
        c._valid = valid
        c.__post_init__()
        return c

    @staticmethod
    def lazy(type_: Type, length: int, thunk, dictionary=None,
             nbytes_hint: int = 0, derived: bool = False) -> "Column":
        """Deferred column: ``thunk()`` returns ``(data, valid)`` and runs
        at most once, on first ``.data``/``.valid`` touch.  ``nbytes_hint``
        feeds byte accounting while unmaterialized (e.g. the host bytes the
        thunk would stage).  ``derived`` marks a wrapper over another lazy
        column (pad/slice composition) so the materialize hook fires once
        per logical column, at the innermost thunk."""
        c = Column.__new__(Column)
        c.type = type_
        c.dictionary = dictionary
        c._enc = "LAZY"
        c._rle_value = None
        c._thunk = thunk
        c._nbytes_hint = int(nbytes_hint)
        c._derived = bool(derived)
        c._data = None
        c._length = int(length)
        c._valid = None
        return c

    # -- encoding accessors --------------------------------------------------

    @property
    def encoding(self) -> str:
        """``FLAT | DICT | RLE | LAZY`` — DICT is a flat code array with a
        dictionary attached (codes ARE the flat representation here)."""
        if self._enc == "FLAT" and self.dictionary is not None:
            return "DICT"
        return self._enc

    @property
    def rle_value(self):
        """The RLE run's stored scalar (storage dtype; code if DICT)."""
        assert self._enc == "RLE"
        return self._rle_value

    @property
    def is_materialized(self) -> bool:
        return self._data is not None or self._enc == "RLE"

    def _materialize(self) -> None:
        if self._data is not None:
            return
        if self._enc == "RLE":
            # zero-copy: a readonly broadcast view over the single value
            self._data = np.broadcast_to(self._rle_value, (self._length,))
            return
        hook = _MATERIALIZE_HOOK
        thunk, self._thunk = self._thunk, None
        data, valid = thunk()
        assert int(data.shape[0]) == self._length, "lazy thunk length"
        self._data = data
        if self._valid is None:
            self._valid = valid
            self.__post_init__()
        self._enc = "FLAT"
        if hook is not None and not self._derived:
            hook("LAZY", self._nbytes_hint or int(data.nbytes))

    @property
    def data(self):
        if self._data is None:
            self._materialize()
        return self._data

    @property
    def valid(self):
        if self._data is None and self._enc == "LAZY":
            self._materialize()
        return self._valid

    def __len__(self) -> int:
        return self._length

    def __repr__(self) -> str:  # debugging aid (dataclass repr equivalent)
        return (f"Column(type={self.type}, encoding={self.encoding}, "
                f"len={self._length})")

    def __reduce__(self):
        # pickling (task descriptors) materializes: thunks don't pickle
        return (Column, (self.type, np.asarray(self.data), self._valid,
                         self.dictionary))

    @property
    def nbytes(self) -> int:
        if self._enc == "RLE":
            n = int(self._rle_value.nbytes)
        elif self._data is None:
            n = self._nbytes_hint
        else:
            n = int(self._data.nbytes)
        if self._valid is not None:
            n += int(self._valid.nbytes)
        return n + _dictionary_nbytes(self.dictionary)

    @property
    def flat_nbytes(self) -> int:
        """Bytes of the EXPANDED flat representation (what legacy execution
        would carry) — the baseline for bytes-saved accounting."""
        itemsize = np.dtype(
            np.int32 if self.dictionary is not None
            else self.type.storage_dtype).itemsize
        n = self._length * itemsize
        if self._valid is not None:
            n += self._length
        return n + _dictionary_nbytes(self.dictionary)

    def valid_mask(self) -> np.ndarray:
        if self.valid is None:
            return np.ones(len(self), dtype=np.bool_)
        return np.asarray(self.valid)

    @staticmethod
    def from_values(type_: Type, values: Sequence) -> "Column":
        """Build a column from python values (None = NULL)."""
        if isinstance(type_, ArrayType):
            codes, valid, dictionary = encode_arrays(values)
            return Column(type_, codes, valid, dictionary)
        if isinstance(type_, DecimalType) and type_.is_long:
            # long decimal: sorted dictionary of python scaled ints
            scaled = [None if v is None else _to_scaled_int(v, type_.scale)
                      for v in values]
            codes, valid, dictionary = encode_sorted_objects(scaled, 0)
            return Column(type_, codes, valid, dictionary)
        if isinstance(type_, RowType):
            canon = [None if v is None else tuple(v) for v in values]
            codes, valid, dictionary = encode_arrays(canon)
            return Column(type_, codes, valid, dictionary)
        if isinstance(type_, MapType):
            canon = [
                None if v is None else tuple(sorted(
                    v.items() if isinstance(v, dict) else v))
                for v in values
            ]
            codes, valid, dictionary = encode_arrays(canon)
            return Column(type_, codes, valid, dictionary)
        if type_.is_dictionary_encoded:
            codes, valid, dictionary = encode_strings(values)
            return Column(type_, codes, valid, dictionary)
        valid = np.array([v is not None for v in values], dtype=np.bool_)
        if isinstance(type_, DecimalType):
            filled = [_to_scaled_int(v, type_.scale) if v is not None else 0
                      for v in values]
        elif type_ == DATE:
            filled = [_to_days(v) if v is not None else 0 for v in values]
        elif type_ == TIMESTAMP:
            filled = [_to_micros(v) if v is not None else 0 for v in values]
        else:
            zero = type_.zero_value()
            filled = [v if v is not None else zero for v in values]
        data = np.asarray(filled, dtype=type_.storage_dtype)
        return Column(type_, data, valid)

    def _empty_flat(self) -> "Column":
        """Zero-row flat column — lets an empty selection over an
        unmaterialized LAZY column skip the thunk entirely."""
        dtype = (np.int32 if self.dictionary is not None
                 else self.type.storage_dtype)
        return Column(self.type, np.empty(0, dtype), None, self.dictionary)

    def take(self, indices: np.ndarray) -> "Column":
        if self._enc == "RLE":
            # a gather over a constant run is still a constant run
            valid = None if self._valid is None else self._valid[indices]
            return Column.rle(self.type, self._rle_value,
                              int(indices.shape[0]), valid, self.dictionary)
        if (self._enc == "LAZY" and self._data is None
                and int(np.asarray(indices).shape[0]) == 0):
            return self._empty_flat()
        # works for numpy and jax alike (jax arrays gather on device)
        valid = None if self.valid is None else self.valid[indices]
        return Column(self.type, self.data[indices], valid, self.dictionary)

    def filter(self, mask: np.ndarray) -> "Column":
        # boolean-mask compaction is inherently dynamic-shape: force host
        mask = np.asarray(mask)
        if self._enc == "RLE":
            valid = (None if self._valid is None
                     else np.asarray(self._valid)[mask])
            return Column.rle(self.type, self._rle_value,
                              int(mask.sum()), valid, self.dictionary)
        if (self._enc == "LAZY" and self._data is None
                and not mask.any()):
            return self._empty_flat()
        valid = None if self.valid is None else np.asarray(self.valid)[mask]
        return Column(self.type, np.asarray(self.data)[mask], valid, self.dictionary)

    def slice_rows(self, start: int, stop: int) -> "Column":
        """Row-range slice with encoding propagation (host path)."""
        if self._enc == "RLE":
            stop = min(stop, self._length)
            valid = (None if self._valid is None
                     else np.asarray(self._valid)[start:stop])
            return Column.rle(self.type, self._rle_value,
                              max(0, stop - start), valid, self.dictionary)
        return Column(self.type, np.asarray(self.data)[start:stop],
                      None if self.valid is None
                      else np.asarray(self.valid)[start:stop],
                      self.dictionary)

    def to_pylist(self) -> list:
        """Decode to python values (None for NULL) — used by clients/oracle."""
        data = np.asarray(self.data)
        valid = self.valid_mask()
        t = self.type
        out: list = []
        if isinstance(t, ArrayType):
            d = self.dictionary
            for i in range(len(self)):
                out.append(list(d[data[i]]) if valid[i] else None)
        elif isinstance(t, DecimalType) and t.is_long:
            d = self.dictionary
            with decimal.localcontext() as ctx:
                ctx.prec = 80  # default 28-digit context rounds wide values
                for i in range(len(self)):
                    out.append(
                        decimal.Decimal(int(d[data[i]])).scaleb(-t.scale)
                        if valid[i] else None)
        elif isinstance(t, RowType):
            d = self.dictionary
            for i in range(len(self)):
                out.append(tuple(d[data[i]]) if valid[i] else None)
        elif isinstance(t, MapType):
            d = self.dictionary
            for i in range(len(self)):
                out.append(dict(d[data[i]]) if valid[i] else None)
        elif t.is_dictionary_encoded:
            d = self.dictionary
            for i in range(len(self)):
                out.append(str(d[data[i]]) if valid[i] else None)
        elif isinstance(t, DecimalType):
            for i in range(len(self)):
                # exact: scaled int -> decimal.Decimal (never through float)
                out.append(
                    decimal.Decimal(int(data[i])).scaleb(-t.scale) if valid[i] else None
                )
        elif t == DATE:
            for i in range(len(self)):
                out.append(days_to_date(data[i]) if valid[i] else None)
        elif t == BOOLEAN:
            for i in range(len(self)):
                out.append(bool(data[i]) if valid[i] else None)
        elif t in (DOUBLE,) or t.name == "real":
            for i in range(len(self)):
                out.append(float(data[i]) if valid[i] else None)
        else:
            for i in range(len(self)):
                out.append(int(data[i]) if valid[i] else None)
        return out


def _to_days(v) -> int:
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, str):
        v = datetime.date.fromisoformat(v)
    return (v - datetime.date(1970, 1, 1)).days


def _to_micros(v) -> int:
    if isinstance(v, (int, np.integer)):
        return int(v)
    if isinstance(v, str):
        v = datetime.datetime.fromisoformat(v)
    if isinstance(v, datetime.datetime):
        epoch = datetime.datetime(1970, 1, 1, tzinfo=v.tzinfo)
        return int((v - epoch) / datetime.timedelta(microseconds=1))
    raise TypeError(f"cannot convert {type(v).__name__} to timestamp")


def rescale_scaled_int(v: int, fs: int, ds: int) -> int:
    """Exact scaled-int rescale with HALF_UP rounding (python bignums,
    80-digit context — the shared Int128Math-style helper for casts and
    aggregate finalization)."""
    if ds >= fs:
        return v * 10 ** (ds - fs)
    with decimal.localcontext() as ctx:
        ctx.prec = 80
        return int(decimal.Decimal(v).scaleb(ds - fs).quantize(
            0, rounding=decimal.ROUND_HALF_UP))


def _to_scaled_int(v, scale: int) -> int:
    """Exact conversion to scaled int64 (never through float64 for exact
    inputs — int/str/Decimal keep full 18-digit precision)."""
    if isinstance(v, (int, np.integer)):
        return int(v) * 10**scale
    if isinstance(v, (str, decimal.Decimal)):
        # default decimal context rounds at 28 digits; wide decimals need
        # the full 38 -> compute under an explicit high-precision context
        with decimal.localcontext() as ctx:
            ctx.prec = 80
            d = decimal.Decimal(v)
            return int((d * 10**scale).to_integral_value(
                rounding=decimal.ROUND_HALF_UP))
    return int(round(float(v) * 10**scale))


def unify_dictionaries(columns: Sequence[Column]) -> list[Column]:
    """Remap a set of dictionary columns onto one shared sorted dictionary.

    Required before concatenating string columns coming from different
    sources.  Host-side; cost is O(total dictionary size)."""
    empty = np.array([], dtype=object)
    dicts = [c.dictionary if c.dictionary is not None else empty for c in columns]
    first = dicts[0]
    if all(d is first or (d.shape == first.shape and (d == first).all()) for d in dicts):
        return list(columns)
    if any(len(d) and isinstance(d[0], tuple) for d in dicts):
        return _unify_object_dictionaries(columns, dicts)
    merged = np.unique(np.concatenate(dicts))
    out = []
    for c, d in zip(columns, dicts):
        remap = np.searchsorted(merged, d).astype(np.int32)
        # no source dictionary => codes are meaningless; point at slot 0
        if not len(d):
            data = np.zeros(len(c), dtype=np.int32)
        elif isinstance(c.data, np.ndarray):
            data = remap[c.data]
        else:  # device codes: gather the (tiny) remap table on device
            import jax.numpy as jnp

            data = jnp.asarray(remap)[c.data]
        out.append(Column(c.type, data, c.valid, merged))
    return out


def _unify_object_dictionaries(columns: Sequence[Column], dicts) -> list[Column]:
    """Array-dictionary variant of unify_dictionaries: tuples with possible
    None elements are not numpy-sortable, so merge with the canonical key."""
    merged_list = sorted({x for d in dicts for x in d}, key=_canon_key)
    pos = {v: i for i, v in enumerate(merged_list)}
    merged = _object_array(merged_list)
    out = []
    for c, d in zip(columns, dicts):
        remap = np.array([pos[v] for v in d], dtype=np.int32)
        if not len(d):
            data = np.zeros(len(c), dtype=np.int32)
        elif isinstance(c.data, np.ndarray):
            data = remap[c.data]
        else:
            import jax.numpy as jnp

            data = jnp.asarray(remap)[c.data]
        out.append(Column(c.type, data, c.valid, merged))
    return out


@dataclass
class ColumnBatch:
    """An ordered, named set of equal-length columns (the Page equivalent).

    ``live`` is an optional per-row mask (True = row exists): the fused
    filter kernels mark rows dead instead of compacting, because compaction
    is a dynamic-shape operation XLA cannot fuse — batches stay at their
    padded power-of-two size through the jitted pipeline (the selection-
    vector idiom replacing Trino's Page.getPositions compaction).  Operators
    either understand ``live`` or call :meth:`compact` first.

    ``resident`` marks a view of storage that outlives the query (a table
    pinned to the device, connectors/memory.py): an operator that holds such
    a batch keeps nothing alive that was not, and accounts nothing for it."""

    names: list[str]
    columns: list[Column]
    live: np.ndarray | None = None  # None = every row live
    resident: bool = field(default=False, compare=False, repr=False)

    def __post_init__(self):
        assert len(self.names) == len(self.columns)
        if self.columns:
            n = len(self.columns[0])
            assert all(len(c) == n for c in self.columns), "ragged batch"

    @property
    def num_rows(self) -> int:
        """Physical row slots (including dead rows when ``live`` is set)."""
        return len(self.columns[0]) if self.columns else 0

    @property
    def live_count(self) -> int:
        """Number of live rows (host sync when ``live`` is a device array)."""
        if self.live is None:
            return self.num_rows
        return int(np.asarray(self.live).sum())

    def to_host(self) -> "ColumnBatch":
        """Materialize every device array with ONE jax.device_get.

        Per-array np.asarray blocks on the device once per array; batching
        the transfer makes the host boundary one wait per batch instead of
        one per column."""
        pending = []
        for c in self.columns:
            if c.encoding == "LAZY":
                continue  # untouched: materializing would defeat laziness
            if not isinstance(c.data, np.ndarray):
                pending.append(c.data)
            if c.valid is not None and not isinstance(c.valid, np.ndarray):
                pending.append(c.valid)
        if self.live is not None and not isinstance(self.live, np.ndarray):
            pending.append(self.live)
        if not pending:
            return self
        import jax

        fetched = iter(jax.device_get(pending))
        cols = []
        for c in self.columns:
            if c.encoding == "LAZY":
                cols.append(c)
                continue
            d = c.data if isinstance(c.data, np.ndarray) else next(fetched)
            v = c.valid
            if v is not None and not isinstance(v, np.ndarray):
                v = next(fetched)
            if c.encoding == "RLE":
                cols.append(Column.rle(c.type, c.rle_value, len(c), v,
                                       c.dictionary))
            else:
                cols.append(Column(c.type, d, v, c.dictionary))
        live = self.live
        if live is not None and not isinstance(live, np.ndarray):
            live = next(fetched)
        return ColumnBatch(self.names, cols, live)

    def compact(self) -> "ColumnBatch":
        """Densify: drop dead rows, return a host-side batch without live."""
        dense = self.to_host()
        if dense.live is None:
            return dense
        mask = np.asarray(dense.live)
        if mask.all():
            return ColumnBatch(dense.names, dense.columns)
        return ColumnBatch(dense.names, [c.filter(mask) for c in dense.columns])

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def nbytes(self) -> int:
        return sum(c.nbytes for c in self.columns)

    def live_nbytes(self, rows: int) -> int:
        """Bytes a dense page of ``rows`` of this batch's rows holds (values
        as they are stored, a run's value and the dictionaries once) -- what
        the planner's statistics mean by a page's bytes, whatever lanes the
        rows ride in.  For an unmasked flat batch of ``rows`` rows this is
        ``nbytes``.  The history table's epoch digests these numbers: take
        them where no other thread can touch a lazy column meanwhile (its
        first touch changes its encoding)."""
        n = 0
        for c in self.columns:
            enc = c._enc
            if enc == "RLE":
                n += int(c._rle_value.nbytes)
                per_row = 0
            elif enc == "LAZY":
                per_row = np.dtype(np.int32 if c.dictionary is not None
                                   else c.type.storage_dtype).itemsize
            else:
                per_row = np.dtype(c._data.dtype).itemsize
            if enc != "LAZY" and c._valid is not None:
                per_row += 1
            n += rows * per_row + _dictionary_nbytes(c.dictionary)
        return n

    def column(self, name: str) -> Column:
        return self.columns[self.names.index(name)]

    @property
    def types(self) -> list[Type]:
        return [c.type for c in self.columns]

    @staticmethod
    def from_pydict(data: dict[str, tuple[Type, Sequence]]) -> "ColumnBatch":
        names = list(data.keys())
        cols = [Column.from_values(t, vals) for (t, vals) in data.values()]
        return ColumnBatch(names, cols)

    def take(self, indices: np.ndarray) -> "ColumnBatch":
        assert self.live is None, "take() on a masked batch (compact first)"
        return ColumnBatch(self.names, [c.take(indices) for c in self.columns])

    def filter(self, mask: np.ndarray) -> "ColumnBatch":
        assert self.live is None, "filter() on a masked batch (compact first)"
        return ColumnBatch(self.names, [c.filter(mask) for c in self.columns])

    def select(self, names: Sequence[str]) -> "ColumnBatch":
        return ColumnBatch(list(names), [self.column(n) for n in names],
                           self.live, self.resident)

    def slice(self, start: int, stop: int) -> "ColumnBatch":
        assert self.live is None, "slice() on a masked batch (compact first)"
        return ColumnBatch(
            self.names,
            [c.slice_rows(start, stop) for c in self.columns],
        )

    @staticmethod
    def concat(batches: Sequence["ColumnBatch"]) -> "ColumnBatch":
        if not batches:
            raise ValueError("ColumnBatch.concat of an empty batch list "
                             "(caller must supply at least the schema batch)")
        batches = [b.compact() for b in batches]
        batches = [b for b in batches if b.num_rows > 0] or list(batches[:1])
        if len(batches) == 1:
            return batches[0]
        names = batches[0].names
        out_cols = []
        for i in range(len(names)):
            cols = [b.columns[i] for b in batches]
            rle = _concat_rle(cols)
            if rle is not None:
                out_cols.append(rle)
                continue
            if cols[0].type.is_dictionary_encoded:
                cols = unify_dictionaries(cols)
            data = np.concatenate([np.asarray(c.data) for c in cols])
            if any(c.valid is not None for c in cols):
                valid = np.concatenate([c.valid_mask() for c in cols])
            else:
                valid = None
            out_cols.append(Column(cols[0].type, data, valid, cols[0].dictionary))
        return ColumnBatch(names, out_cols)

    def to_pylist(self) -> list[tuple]:
        """Rows as python tuples (client/oracle boundary)."""
        dense = self.compact()
        cols = [c.to_pylist() for c in dense.columns]
        return list(zip(*cols)) if cols else []

    def rename(self, names: Sequence[str]) -> "ColumnBatch":
        return ColumnBatch(list(names), self.columns, self.live,
                           self.resident)


def _same_dictionary(a, b) -> bool:
    if a is None or b is None:
        return a is b
    return a is b or (a.shape == b.shape and (a == b).all())


def _concat_rle(cols: Sequence[Column]):
    """One RLE column covering a concatenation of same-value runs, or None
    when the inputs aren't a single mergeable run."""
    if not all(c.encoding == "RLE" for c in cols):
        return None
    first = cols[0]
    for c in cols[1:]:
        if (c.rle_value != first.rle_value
                or not _same_dictionary(c.dictionary, first.dictionary)):
            return None
    total = sum(len(c) for c in cols)
    if all(c.valid is None for c in cols):
        valid = None
    else:
        valid = np.concatenate([c.valid_mask() for c in cols])
    return Column.rle(first.type, first.rle_value, total, valid,
                      first.dictionary)


# RLE page-build detection floor: below this a run saves nothing worth the
# check; the two-element probe keeps the reject path O(1)
RLE_DETECT_MIN_ROWS = 64


def maybe_rle(col: Column) -> Column:
    """Cheap constant-run detection at page build: a dense host column whose
    every element equals its first collapses to RLE.  O(1) reject via a
    first/last probe before the full equality scan; non-FLAT/DICT and
    device columns pass through untouched."""
    if col.encoding not in ("FLAT", "DICT") or len(col) < RLE_DETECT_MIN_ROWS:
        return col
    data = col._data
    if not isinstance(data, np.ndarray) or data.dtype == object:
        return col
    if data[0] != data[-1] or not (data == data[0]).all():
        return col
    if col.valid is not None and not isinstance(col.valid, np.ndarray):
        return col
    return Column.rle(col.type, data[0], len(col), col.valid, col.dictionary)


def pad_to_bucket(batch: ColumnBatch) -> ColumnBatch:
    """Pad a dense batch to its power-of-two row bucket, marking the padding
    dead in ``live``.  A batch that already carries a ``live`` mask is
    already bucket-shaped (device-pinned tables / jitted pipeline output):
    passed through untouched.  Device-resident columns pad with device ops
    (async, no host round trip); host columns pad in numpy."""
    if batch.live is not None:
        return batch
    n = batch.num_rows
    cap = round_up_pow2(n)
    if cap == n or n == 0:
        return batch
    pad = cap - n
    on_device = any(c.encoding not in ("RLE", "LAZY")
                    and not isinstance(c.data, np.ndarray)
                    for c in batch.columns)

    def _pad_encoded(c: Column):
        """RLE extends its run over the dead pad rows; LAZY composes a
        padding thunk — neither expands."""
        if c.encoding == "RLE":
            valid = c.valid
            if valid is not None:
                if isinstance(valid, np.ndarray):
                    valid = np.concatenate(
                        [valid, np.zeros(pad, np.bool_)])
                else:
                    import jax.numpy as jnp

                    valid = jnp.concatenate(
                        [valid, jnp.zeros(pad, jnp.bool_)])
            return Column.rle(c.type, c.rle_value, cap, valid, c.dictionary)
        if c.encoding == "LAZY":
            def thunk(c=c):
                data = np.concatenate(
                    [np.asarray(c.data),
                     np.zeros(pad, np.asarray(c.data).dtype)])
                valid = None
                if c.valid is not None:
                    valid = np.concatenate(
                        [np.asarray(c.valid), np.zeros(pad, np.bool_)])
                return data, valid

            return Column.lazy(c.type, cap, thunk, c.dictionary,
                               nbytes_hint=c.nbytes, derived=True)
        return None

    if on_device:
        import jax.numpy as jnp

        cols = []
        for c in batch.columns:
            enc = _pad_encoded(c)
            if enc is not None:
                cols.append(enc)
                continue
            data = jnp.concatenate(
                [jnp.asarray(c.data), jnp.zeros(pad, jnp.asarray(c.data).dtype)])
            valid = None
            if c.valid is not None:
                valid = jnp.concatenate(
                    [jnp.asarray(c.valid), jnp.zeros(pad, jnp.bool_)])
            cols.append(Column(c.type, data, valid, c.dictionary))
        live = jnp.concatenate(
            [jnp.ones(n, jnp.bool_), jnp.zeros(pad, jnp.bool_)])
        return ColumnBatch(batch.names, cols, live)
    cols = []
    for c in batch.columns:
        enc = _pad_encoded(c)
        if enc is not None:
            cols.append(enc)
            continue
        data = np.asarray(c.data)
        data = np.concatenate([data, np.zeros(pad, data.dtype)])
        valid = None
        if c.valid is not None:
            valid = np.concatenate([np.asarray(c.valid), np.zeros(pad, np.bool_)])
        cols.append(Column(c.type, data, valid, c.dictionary))
    live = np.concatenate([np.ones(n, np.bool_), np.zeros(pad, np.bool_)])
    return ColumnBatch(batch.names, cols, live)
