"""TPC-DS tables from a seed, for the columns that TPC-DS Q36 reads: the
benchmark's own generator, so that a program PR cannot move the yardstick.
Not `dsdgen`: the specification's row counts and shapes, numpy's random
streams.

  * row counts at the specification's scale factors: store_sales 2,880,404
    at SF1, 28,800,991 at SF10, 287,997,024 at SF100; item 18,000 / 102,000
    / 204,000; store 12 / 102 / 402; at any other scale (the tests' and the
    dry run's) SF1's share.  date_dim does not scale: 73,049 rows, every day
    from 1900-01-02 to 2100-01-01, `d_date_sk` its Julian day number
    (2,415,022 .. 2,488,070), `d_year` its year;
  * store_sales is sold on a day drawn uniformly from dsdgen's five sales
    years, 1998-01-01 .. 2002-12-31, so that one `d_year` keeps a fifth;
    `ss_item_sk` uniform over the items, `ss_store_sk` uniform over the
    stores and null in 4% of the rows;
  * money is decimal(7,2) in exact cents, by the price chain of
    `spark_rapids_tpu.tpcds.gen_tables`: wholesale cost 1.00 .. 99.99, list
    price = wholesale x 1.10 .. 2.99, sales price = list x 0.30 .. 1.00
    (each truncated to cents), quantity 1 .. 100, ext_sales_price = sales
    price x quantity, a coupon on a tenth of the rows (5 .. 29% of
    ext_sales_price), net paid = ext_sales_price - coupon, and
    ss_net_profit = net paid - wholesale x quantity, which can be negative;
  * item's hierarchy nested as dsdgen nests it: `i_category` one of ten,
    `i_class` one of that category's own classes, four or sixteen
    (`CLASSES`: 100 pairs, 99 distinct class names, "kids" under both Shoes
    and Home), the pair uniform over the 100;
  * store's `s_state` cycles through eight states, those Q36 selects.

Only the columns asked for are made (`columns={"store_sales": [...]}`),
every random draw has a stream of its own keyed by (seed, name), so leaving
a column out does not change another, and no row is made in a Python loop.
"""
from __future__ import annotations

import datetime as pydt
import zlib
from typing import Callable, Dict, Iterable, Mapping

import numpy as np
import pyarrow as pa

from data.tpch_gen import money

# dsdgen's category -> classes distribution (its spelling kept)
CLASSES = {
    "Women": ["dresses", "fragrances", "maternity", "swimwear"],
    "Men": ["accessories", "pants", "shirts", "sports-apparel"],
    "Children": ["infants", "newborn", "school-uniforms", "toddlers"],
    "Shoes": ["athletic", "kids", "mens", "womens"],
    "Music": ["classical", "country", "pop", "rock"],
    "Jewelry": ["birdal", "bracelets", "consignment", "costume", "custom",
                "diamonds", "earings", "estate", "gold", "jewelry boxes",
                "loose stones", "mens watch", "pendants", "rings",
                "semi-precious", "womens watch"],
    "Home": ["accent", "bathroom", "bedding", "blinds/shades",
             "curtains/drapes", "decor", "flatware", "furniture",
             "glassware", "kids", "lighting", "mattresses", "paint", "rugs",
             "tables", "wallpaper"],
    "Sports": ["archery", "athletic shoes", "baseball", "basketball",
               "camping", "fishing", "fitness", "football", "golf", "guns",
               "hockey", "optics", "outdoor", "pools", "sailing", "tennis"],
    "Books": ["arts", "business", "computers", "cooking", "entertainments",
              "fiction", "history", "home repair", "mystery", "parenting",
              "reference", "romance", "science", "self-help", "sports",
              "travel"],
    "Electronics": ["audio", "automotive", "camcorders", "cameras",
                    "disk drives", "dvd/vcr players", "karoke", "memory",
                    "monitors", "musical", "personal", "portable",
                    "scanners", "stereo", "televisions", "wireless"],
}
PAIRS = [(c, k) for c, ks in CLASSES.items() for k in ks]
STATES = ["TN", "SC", "AL", "GA", "SD", "MI", "OH", "TX"]

ROWS = {1: {"store_sales": 2_880_404, "item": 18_000, "store": 12},
        10: {"store_sales": 28_800_991, "item": 102_000, "store": 102},
        100: {"store_sales": 287_997_024, "item": 204_000, "store": 402}}
DATE_FIRST = pydt.date(1900, 1, 2)
DATE_ROWS = 73_049
SALES_FIRST = pydt.date(1998, 1, 1)
SALES_LAST = pydt.date(2002, 12, 31)
STORE_NULL_SHARE = 0.04
# date.toordinal() + this = the Julian day number (2000-01-01 -> 2451545)
_JDN = 1_721_425


def julian(d: pydt.date) -> int:
    return d.toordinal() + _JDN


def row_counts(scale: float) -> Dict[str, int]:
    if scale in ROWS:
        counts = dict(ROWS[scale])
    else:
        counts = {name: max(int(n * scale), least) for (name, n), least
                  in zip(ROWS[1].items(), (1_000, 100, 8))}
    counts["date_dim"] = DATE_ROWS
    return counts


class _Gen:
    def __init__(self, scale: float, seed: int):
        self.n = row_counts(scale)
        self.seed = int(seed)
        self._raw: Dict[str, np.ndarray] = {}

    def rng(self, key: str) -> np.random.Generator:
        return np.random.default_rng(
            [self.seed, zlib.crc32(key.encode("ascii"))])

    def raw(self, key: str, make: Callable[[], np.ndarray]) -> np.ndarray:
        """A numpy column kept for the columns derived from it."""
        if key not in self._raw:
            self._raw[key] = make()
        return self._raw[key]

    def draw(self, key: str, lo: int, hi: int, table: str) -> np.ndarray:
        """Uniform whole numbers lo..hi, both ends in, one per row (kept)."""
        return self.raw(key, lambda: self.rng(key).integers(
            lo, hi + 1, self.n[table], dtype=np.int64))

    # -- the price chain, in cents ------------------------------------------
    def quantity(self):
        return self.draw("store_sales.quantity", 1, 100, "store_sales")

    def wholesale(self):
        return self.draw("store_sales.wholesale", 100, 9_999, "store_sales")

    def sales_price(self):
        def make():
            markup = self.draw("store_sales.markup", 110, 299,
                               "store_sales")
            cut = self.draw("store_sales.cut", 30, 100, "store_sales")
            return (self.wholesale() * markup // 100) * cut // 100
        return self.raw("store_sales.sales_price", make)

    def ext_sales_price(self):
        return self.raw("store_sales.ext_sales_price",
                        lambda: self.sales_price() * self.quantity())

    def net_profit(self):
        ext = self.ext_sales_price()
        n = self.n["store_sales"]
        coupon_rows = self.rng("store_sales.coupon_rows").random(n) < 0.1
        share = self.rng("store_sales.coupon_share").integers(
            5, 30, n, dtype=np.int64)
        coupon = np.where(coupon_rows, ext * share // 100, 0)
        return ext - coupon - self.wholesale() * self.quantity()

    # -- keys -----------------------------------------------------------------
    def store_key(self) -> pa.Array:
        n = self.n["store_sales"]
        keys = self.draw("store_sales.ss_store_sk", 1, self.n["store"],
                         "store_sales")
        null = self.rng("store_sales.ss_store_sk.null").random(n) \
            < STORE_NULL_SHARE
        return pa.array(keys, pa.int64(), mask=null)

    def item_pair(self) -> np.ndarray:
        """Each item's index into PAIRS."""
        return self.raw("item.pair", lambda: self.rng("item.pair").integers(
            0, len(PAIRS), self.n["item"], dtype=np.int64))


def _decimal72(cents: np.ndarray) -> pa.Array:
    if len(cents) and np.abs(cents).max() > 9_999_999:
        raise ValueError("a money value does not fit decimal(7,2)")
    return money(cents, 7, 2)


def _strings(values, index: np.ndarray) -> pa.Array:
    """values[index] as a string column, without a Python loop a row."""
    return pa.DictionaryArray.from_arrays(
        pa.array(index.astype(np.int32)), pa.array(values)).cast(pa.string())


def _days() -> np.ndarray:
    return np.arange(DATE_ROWS, dtype=np.int64) + julian(DATE_FIRST)


def _years() -> np.ndarray:
    """d_year of every date_dim row: from the first day of each year."""
    starts = np.array([julian(pydt.date(y, 1, 1)) for y in range(1900, 2102)])
    return (1900 + np.searchsorted(starts, _days(), side="right") - 1
            ).astype(np.int32)


COLUMNS: Dict[str, Dict[str, Callable[[_Gen], pa.Array]]] = {
    "store_sales": {
        "ss_sold_date_sk": lambda g: pa.array(g.draw(
            "store_sales.ss_sold_date_sk", julian(SALES_FIRST),
            julian(SALES_LAST), "store_sales"), pa.int64()),
        "ss_item_sk": lambda g: pa.array(g.draw(
            "store_sales.ss_item_sk", 1, g.n["item"], "store_sales"),
            pa.int64()),
        "ss_store_sk": lambda g: g.store_key(),
        "ss_ext_sales_price": lambda g: _decimal72(g.ext_sales_price()),
        "ss_net_profit": lambda g: _decimal72(g.net_profit()),
    },
    "date_dim": {
        "d_date_sk": lambda g: pa.array(_days(), pa.int64()),
        "d_year": lambda g: pa.array(_years(), pa.int32()),
    },
    "item": {
        "i_item_sk": lambda g: pa.array(
            np.arange(1, g.n["item"] + 1, dtype=np.int64), pa.int64()),
        "i_category": lambda g: _strings(
            list(CLASSES), np.array([list(CLASSES).index(c)
                                     for c, _k in PAIRS])[g.item_pair()]),
        "i_class": lambda g: _strings([k for _c, k in PAIRS],
                                      g.item_pair()),
    },
    "store": {
        "s_store_sk": lambda g: pa.array(
            np.arange(1, g.n["store"] + 1, dtype=np.int64), pa.int64()),
        "s_state": lambda g: _strings(
            STATES, np.arange(g.n["store"]) % len(STATES)),
    },
}


def gen_tables(scale: float, seed: int,
               columns: Mapping[str, Iterable[str]]) -> Dict[str, pa.Table]:
    """The asked-for columns of the asked-for tables, in the order asked."""
    g = _Gen(scale, seed)
    out = {}
    for table, names in columns.items():
        if table not in COLUMNS:
            raise KeyError(f"tpcds_gen has no table {table!r}")
        made = {}
        for name in names:
            if name not in COLUMNS[table]:
                raise KeyError(f"tpcds_gen has no column {table}.{name}")
            made[name] = COLUMNS[table][name](g)
        out[table] = pa.table(made)
    return out
