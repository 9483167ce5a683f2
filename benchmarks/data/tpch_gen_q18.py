"""TPC-H tables for Q18 (clause 2.4.18): `tpch_gen`'s tables, plus the two
columns Q18 reads that `tpch_gen` does not make.

Every column `tpch_gen` makes is `tpch_gen`'s own: the same `_Gen`, the same
random streams keyed by (seed, name), the same values for a seed.  Added, as
clause 4.2.3 defines them:

  * c_name: "Customer#" and the key as nine digits, zero-padded
    (4.2.3: `C_NAME text appended with digit ["Customer", C_CUSTKEY]`);
  * o_totalprice: the sum over the order's lines of
    l_extendedprice * (1 + l_tax) * (1 - l_discount), decimal(12,2).  The
    product of the three is exact at six decimal places in integers; the
    order's exact sum is rounded ONCE, half up, to cents (dbgen truncates
    each line's product twice instead: `assumed` in the configuration).
    l_tax and l_discount are read from `tpch_gen`'s streams whether or not
    the columns themselves are asked for.
"""
from __future__ import annotations

from typing import Dict, Iterable, Mapping

import numpy as np
import pyarrow as pa

from data import tpch_gen as base
from data.tpch_gen import days, row_counts          # noqa: F401  (re-export)

NAME_PREFIX = b"Customer#"
NAME_DIGITS = 9


def customer_names(keys: np.ndarray) -> pa.Array:
    """"Customer#000000001", ... as a string column, without a Python loop:
    every name is 18 bytes."""
    keys = np.asarray(keys, dtype=np.int64)
    width = len(NAME_PREFIX) + NAME_DIGITS
    data = np.empty((len(keys), width), dtype=np.uint8)
    data[:, :len(NAME_PREFIX)] = np.frombuffer(NAME_PREFIX, dtype=np.uint8)
    rest = keys.copy()
    for place in range(width - 1, len(NAME_PREFIX) - 1, -1):
        data[:, place] = 48 + rest % 10
        rest //= 10
    if rest.any():
        raise ValueError("a customer key has more than nine digits")
    offsets = np.arange(len(keys) + 1, dtype=np.int32) * width
    return pa.Array.from_buffers(
        pa.string(), len(keys),
        [None, pa.py_buffer(offsets), pa.py_buffer(data.reshape(-1))])


def total_price_cents(g: base._Gen) -> np.ndarray:
    """o_totalprice in cents, one per order, exact integers throughout."""
    tax = g.draw("lineitem.l_tax", 0, 8, "lineitem")
    discount = g.draw("lineitem.l_discount", 0, 10, "lineitem")
    # cents x hundredths x hundredths: six decimal places, under 2^40
    line = g.l_price() * (100 + tax) * (100 - discount)
    lines = g.lines()
    starts = np.cumsum(lines) - lines            # every order has a line
    exact = np.add.reduceat(line, starts)
    return (exact + 5_000) // 10_000             # half up: nothing negative


EXTRA = {
    "customer": {
        "c_name": lambda g: customer_names(
            np.arange(1, g.n["customer"] + 1)),
    },
    "orders": {
        "o_totalprice": lambda g: base.money(total_price_cents(g)),
    },
}


def gen_tables(scale: float, seed: int,
               columns: Mapping[str, Iterable[str]]) -> Dict[str, pa.Table]:
    """The asked-for columns of the asked-for tables, in the order asked:
    `tpch_gen`'s maker where it has one, else this module's."""
    g = base._Gen(scale, seed)
    out = {}
    for table, names in columns.items():
        if table not in base.COLUMNS:
            raise KeyError(f"tpch_gen_q18 has no table {table!r}")
        made = {}
        for name in names:
            maker = base.COLUMNS[table].get(name) \
                or EXTRA.get(table, {}).get(name)
            if maker is None:
                raise KeyError(f"tpch_gen_q18 has no column {table}.{name}")
            made[name] = maker(g)
        out[table] = pa.table(made)
    return out
