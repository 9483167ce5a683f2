"""Plain column arithmetic for the query references, and the byte counts
behind `xla_programs_roofline`.  numpy and pyarrow only: nothing of the engine.

Money is held as unscaled int64 (cents), so every sum is exact.  The
lower-precision control asks for the same columns as float32."""
from __future__ import annotations

import decimal
from typing import Dict, Iterable, Mapping, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def _one_chunk(col) -> pa.Array:
    if not isinstance(col, pa.ChunkedArray):
        return col
    # combine_chunks copies even a single chunk: gigabytes at SF10
    return col.chunk(0) if col.num_chunks == 1 else col.combine_chunks()


def ints(col) -> np.ndarray:
    """An integer or date32 column as numpy (dates as days since 1970)."""
    arr = _one_chunk(col)
    if pa.types.is_date32(arr.type):
        arr = arr.cast(pa.int32())
    return arr.to_numpy(zero_copy_only=False)


def cents(col, dtype=np.int64) -> np.ndarray:
    """A decimal column's unscaled values (the low 64-bit lane of each
    decimal128; the generator makes nothing wider), as `dtype`."""
    arr = _one_chunk(col)
    if not pa.types.is_decimal128(arr.type):
        raise TypeError(f"cents() wants a decimal128 column, got {arr.type}")
    if arr.null_count:
        raise ValueError("cents() wants a column without nulls")
    lanes = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    low = lanes[2 * arr.offset: 2 * (arr.offset + len(arr)): 2]
    return low if dtype is np.int64 else low.astype(dtype)


def whole(x) -> int:
    """A sum as an exact Python int (a float32/float64 control rounds
    here, once, after its arithmetic)."""
    return int(x) if isinstance(x, (int, np.integer)) else int(round(float(x)))


def decimals(unscaled: Iterable, scale: int) -> pa.Array:
    """Python ints (unscaled) -> decimal128(38, scale)."""
    return pa.array([decimal.Decimal(whole(v)).scaleb(-scale)
                     for v in unscaled], pa.decimal128(38, scale))


def lookup(primary: np.ndarray, foreign: np.ndarray) -> np.ndarray:
    """Row of `primary` (unique keys) that each foreign key names, or -1."""
    order = np.argsort(primary, kind="stable")
    ranked = primary[order]
    pos = np.searchsorted(ranked, foreign)
    pos[pos == len(ranked)] = 0
    hit = ranked[pos] == foreign
    return np.where(hit, order[pos], -1)


def group_sum(keys: np.ndarray, values: np.ndarray):
    """-> (unique keys ascending, the sum of `values` in each), in the
    dtype of `values` (exact for int64)."""
    order = np.argsort(keys, kind="stable")
    ranked = keys[order]
    if not len(ranked):
        return ranked, values[:0]
    starts = np.flatnonzero(np.r_[True, ranked[1:] != ranked[:-1]])
    return ranked[starts], np.add.reduceat(values[order], starts)


# -- bytes a sound program must move ---------------------------------------

def _fixed_width(lo: int, hi: int) -> float:
    """Bytes of the narrowest of 1/2/4/8/16-byte integers that holds
    [lo, hi] (unsigned where nothing is negative)."""
    for nbytes in (1, 2, 4, 8):
        top = 1 << (8 * nbytes)
        if (0 <= lo and hi < top) or (-top // 2 <= lo and hi < top // 2):
            return float(nbytes)
    return 16.0


def column_width(col) -> float:
    """Bytes a row of this column must cost a sound program: the narrowest
    fixed-width integer that holds every value the column has (money as
    unscaled cents, dates as days); a string its dictionary code where it
    has at most 65,536 distinct values, else its mean byte length + 4.

    Not the declared type's width (date32 4, decimal(12,2) 8): the engine
    already holds q6's four columns in 13.9 B a row on the chip, under the
    28 B of their declared types (PERF.md section 6, PR 24), and a count
    above what a sound program must read would let a roofline share pass
    100%."""
    col = _one_chunk(col)
    t = col.type
    if not len(col):
        return 0.0
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        distinct = pc.count_distinct(col).as_py()
        if distinct <= 65_536:
            return _fixed_width(0, distinct - 1)
        return col.buffers()[2].size / len(col) + 4.0
    if pa.types.is_decimal128(t) and not col.null_count:
        lanes = np.frombuffer(col.buffers()[1], dtype=np.int64)
        high = lanes[2 * col.offset + 1: 2 * (col.offset + len(col)): 2]
        low = lanes[2 * col.offset: 2 * (col.offset + len(col)): 2]
        if np.array_equal(high, low >> 63):      # every value fits 64 bits
            return _fixed_width(int(low.min()), int(low.max()))
        return 16.0
    if pa.types.is_integer(t) or pa.types.is_date32(t):
        values = ints(col)
        return _fixed_width(int(values.min()), int(values.max()))
    if pa.types.is_boolean(t):
        return 1.0
    return t.bit_width / 8.0


def table_bytes(table: pa.Table, names: Sequence[str] = None) -> int:
    names = table.schema.names if names is None else names
    return int(round(sum(table.num_rows * column_width(table[n])
                         for n in names)))


def needed_bytes(tables: Mapping[str, pa.Table],
                 source_columns: Mapping[str, Sequence[str]],
                 answer: pa.Table) -> int:
    """Every source column the query reads, read once, plus its answer: the
    same work whatever implements it."""
    return sum(table_bytes(tables[t], cols)
               for t, cols in source_columns.items()) + table_bytes(answer)


def merge_columns(specs: Iterable[Mapping[str, Sequence[str]]]
                  ) -> Dict[str, list]:
    """Union of several queries' source columns, first-seen order."""
    out: Dict[str, list] = {}
    for spec in specs:
        for table, cols in spec.items():
            have = out.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    return out
