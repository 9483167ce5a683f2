"""TPC-H tables from a seed, populated as clause 4.2.3 of the specification
(rev 3) says, for the columns that the shipped queries read (q1, q3, q5, q6,
q13).  The benchmark's own generator, so that a program PR cannot move the
yardstick.  Not `dbgen`: the same rules, numpy's random streams.

What follows the specification, rule for rule:

  * row counts: orders 1,500,000 x SF, customer 150,000 x SF, supplier
    10,000 x SF, lineitem the specification's own count at its scale factors
    (6,001,215 at SF1, 59,986,052 at SF10, ...).  Every seed gives the same
    counts, so one compile cache serves every seed;
  * keys start at 1; o_orderkey is sparse (the first 8 of every 32 keys);
    o_custkey is never a multiple of 3, so a third of the customers have no
    orders;
  * each order has 1 to 7 lines, clustered by order key in lineitem;
    l_shipdate = o_orderdate + 1..121 days, l_commitdate = o_orderdate +
    30..90, l_receiptdate = l_shipdate + 1..30; l_returnflag and l_linestatus
    follow from those dates and CURRENTDATE 1995-06-17;
  * l_partkey random, l_suppkey one of the part's four suppliers by the
    specification's formula, l_extendedprice = l_quantity x the part's
    retail price (the formula on p_partkey);
  * o_comment is a text string [19, 78]: a substring, at a random offset and
    of random length, of a pool of sentences made by the pseudo text grammar
    of clause 4.2.2.14 from its word lists.

Where it had to depart (the configurations list these under `assumed`): the
lines per order are drawn 1..7 and then a few orders (under 0.2%) move by
one line so that the total is the specification's count; the text pool is
4 MB, not 300 MB; the word weights are not dbgen's `dists.dss` (not to hand
without a network) but weights of the same shape, set so that the share of
orders whose comment is LIKE '%special%requests%' is the 1.07% that TPC-H's
published Q13 answer implies.

Only the columns asked for are made (`columns={"lineitem": [...]}`), and
every random draw has a stream of its own, keyed by (seed, name), so leaving
a column out does not change another.
"""
from __future__ import annotations

import datetime as pydt
import itertools
import zlib
from typing import Callable, Dict, Iterable, List, Mapping, Tuple

import numpy as np
import pyarrow as pa

_DATE0 = pydt.date(1970, 1, 1)


def days(d: pydt.date) -> int:
    return (d - _DATE0).days


STARTDATE = days(pydt.date(1992, 1, 1))
ENDDATE = days(pydt.date(1998, 12, 31))
CURRENTDATE = days(pydt.date(1995, 6, 17))

NATIONS = ["ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA",
           "FRANCE", "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN",
           "JORDAN", "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA",
           "ROMANIA", "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM",
           "UNITED STATES"]
REGION_OF = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0, 0, 0, 1, 2, 3, 4,
             2, 3, 3, 1]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]

# lineitem rows at the specification's scale factors (clause 4.2.5); at any
# other scale (the tests' and the dry run's) SF1's share
LINEITEM_ROWS = {1: 6_001_215, 10: 59_986_052, 30: 179_998_372,
                 100: 600_037_902}


def row_counts(scale: float) -> Dict[str, int]:
    n_orders = max(int(1_500_000 * scale), 40)
    n_li = LINEITEM_ROWS.get(scale, max(int(6_001_215 * scale), 100))
    return {"lineitem": min(max(n_li, n_orders), 7 * n_orders),
            "orders": n_orders,
            "customer": max(int(150_000 * scale), 20),
            "supplier": max(int(10_000 * scale), 5),
            "part": max(int(200_000 * scale), 20),
            "nation": 25, "region": 5}


# -- the pseudo text grammar of clause 4.2.2.14 -----------------------------
# category -> (words, weights).  A word is written with its leading space; a
# terminator has none, so it attaches to the word before it.
_FEW, _SOME, _MANY = 1, 10, 30
WORDS: Dict[str, Tuple[List[str], List[int]]] = {
    "N": (["packages", "requests", "accounts", "deposits", "foxes", "ideas",
           "theodolites", "pinto beans", "instructions", "dependencies",
           "excuses", "platelets", "asymptotes", "courts", "dolphins",
           "multipliers", "sauternes", "warthogs", "frets", "dinos",
           "attainments", "somas", "Tiresias", "patterns", "forges",
           "braids", "hockey players", "frays", "warhorses", "dugouts",
           "notornis", "epitaphs", "pearls", "tithes", "waters", "orbits",
           "gifts", "sheaves", "depths", "sentiments", "decoys", "realms",
           "pains", "grouches", "escapades"],
          [_MANY] * 4 + [_SOME] * 9 + [_FEW] * 32),
    "J": (["special", "pending", "unusual", "express", "regular", "final",
           "ironic", "even", "bold", "silent", "furious", "sly", "careful",
           "blithe", "quick", "fluffy", "slow", "quiet", "ruthless", "thin",
           "close", "dogged", "daring", "brave", "stealthy", "permanent",
           "enticing", "idle", "busy"],
          [13] * 4 + [_MANY] * 4 + [_SOME] * 2 + [_FEW] * 19),
    "D": (["sometimes", "always", "never", "furiously", "slyly", "carefully",
           "blithely", "quickly", "fluffily", "slowly", "quietly",
           "ruthlessly", "thinly", "closely", "doggedly", "daringly",
           "bravely", "stealthily", "permanently", "enticingly", "idly",
           "busily", "regularly", "finally", "ironically", "evenly",
           "boldly", "silently"],
          [_FEW] * 3 + [_MANY] * 3 + [_SOME] * 5 + [_FEW] * 11 + [_SOME] * 6),
    "V": (["sleep", "wake", "are", "cajole", "haggle", "nag", "use", "boost",
           "affix", "detect", "integrate", "maintain", "nod", "was", "lose",
           "sublate", "solve", "thrash", "promise", "engage", "hinder",
           "print", "x-ray", "breach", "eat", "grow", "impress", "mold",
           "poach", "serve", "run", "dazzle", "snooze", "doze", "unwind",
           "kindle", "play", "hang", "believe", "doubt"],
          [_MANY] * 6 + [_SOME] * 8 + [_FEW] * 26),
    "P": (["about", "above", "according to", "across", "after", "against",
           "along", "alongside of", "among", "around", "at", "atop",
           "before", "behind", "beneath", "beside", "besides", "between",
           "beyond", "by", "despite", "during", "except", "for", "from",
           "in place of", "inside", "instead of", "into", "near", "of", "on",
           "outside", "over", "past", "since", "through", "throughout", "to",
           "toward", "under", "until", "up", "upon", "without", "with",
           "within"],
          [_SOME] * 47),
    "X": (["do", "may", "might", "shall", "will", "would", "can", "could",
           "should", "ought to", "must", "will have to", "shall have to",
           "could have to", "should have to", "must have to", "need to",
           "try to"],
          [_SOME] * 18),
    "T": ([".", ";", ":", "?", "!", "--"], [50, 1, 1, 1, 1, 1]),
}
# noun phrase, verb phrase and sentence forms with dbgen's weights; "Jc" is
# an adjective followed by a comma, "the" the article of a prepositional
# phrase
_NP = [(["N"], 10), (["J", "N"], 20), (["Jc", "J", "N"], 10),
       (["D", "J", "N"], 50)]
_VP = [(["V"], 30), (["X", "V"], 1), (["V", "D"], 40), (["X", "V", "D"], 1)]
_SENTENCE = [("NP VP T", 3), ("NP VP PP T", 3), ("NP VP NP T", 3),
             ("NP PP VP NP T", 1), ("NP PP VP PP T", 1)]
POOL_BYTES = 4 << 20


def _templates() -> Tuple[List[List[str]], np.ndarray]:
    """Every sentence as a sequence of word categories, with its share."""
    out, shares = [], []
    for form, weight in _SENTENCE:
        slots = []
        for part in form.split():
            if part == "NP":
                slots.append(_NP)
            elif part == "VP":
                slots.append(_VP)
            elif part == "PP":
                slots.append([(["P", "the"] + np_, w) for np_, w in _NP])
            else:
                slots.append([([part], 1)])
        for combo in itertools.product(*slots):
            out.append([c for piece, _w in combo for c in piece])
            shares.append(weight * np.prod(
                [w / sum(x for _p, x in slot)
                 for (_piece, w), slot in zip(combo, slots)]))
    shares = np.asarray(shares, dtype=np.float64)
    return out, shares / shares.sum()


def text_pool(rng: np.random.Generator, nbytes: int = POOL_BYTES
              ) -> np.ndarray:
    """`nbytes` of grammar text as uint8, without a Python loop over words."""
    vocab: List[bytes] = []
    first: Dict[str, int] = {}
    for cat, (words, _w) in WORDS.items():
        first[cat] = len(vocab)
        vocab += [(w if cat == "T" else " " + w).encode("ascii")
                  for w in words]
    first["Jc"] = len(vocab)
    vocab += [(" " + w + ",").encode("ascii") for w in WORDS["J"][0]]
    first["the"] = len(vocab)
    vocab.append(b" the")
    cats = list(first)
    word_len = np.array([len(v) for v in vocab], dtype=np.int64)
    word_at = np.concatenate([[0], np.cumsum(word_len)[:-1]])
    letters = np.frombuffer(b"".join(vocab), dtype=np.uint8)

    templates, shares = _templates()
    width = max(len(t) for t in templates)
    grid = np.full((len(templates), width), -1, dtype=np.int64)
    for i, t in enumerate(templates):
        grid[i, :len(t)] = [cats.index(c) for c in t]
    # no word with its space is under 3 bytes: more sentences than needed
    words_a_sentence = float((shares * [len(t) for t in templates]).sum())
    n_sentences = int(nbytes / (3 * words_a_sentence)) + 16
    picked = grid[rng.choice(len(templates), n_sentences, p=shares)]
    stream = picked[picked >= 0]                 # categories, sentence order
    words = np.empty(len(stream), dtype=np.int64)
    for ci, cat in enumerate(cats):
        at = np.flatnonzero(stream == ci)
        if cat == "the":
            words[at] = first[cat]
            continue
        names, weights = WORDS["J" if cat == "Jc" else cat]
        p = np.asarray(weights, dtype=np.float64)
        words[at] = first[cat] + rng.choice(len(names), len(at),
                                            p=p / p.sum())
    lens = word_len[words]
    ends = np.cumsum(lens)
    if ends[-1] < nbytes:
        raise AssertionError("text pool came out short")
    words = words[:np.searchsorted(ends, nbytes) + 1]
    return _gather(letters, word_at[words], word_len[words])[:nbytes]


def _gather(source: np.ndarray, starts: np.ndarray, lens: np.ndarray
            ) -> np.ndarray:
    """source[starts[i] : starts[i] + lens[i]] for every i, end to end."""
    out_at = np.cumsum(lens) - lens
    index = np.repeat(starts - out_at, lens)
    index += np.arange(len(index), dtype=index.dtype)
    return source[index]


def text_strings(pool: np.ndarray, rng: np.random.Generator, n: int,
                 lo: int, hi: int, block: int = 1 << 18) -> pa.Array:
    """n text strings [lo, hi] (clause 4.2.2.10): substrings of the pool at
    a random offset, of a random length."""
    lens = rng.integers(lo, hi + 1, n, dtype=np.int64)
    starts = rng.integers(0, len(pool) - hi, n, dtype=np.int64)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    if offsets[-1] >= 2**31:
        raise ValueError("text column over 2 GiB: lower the scale")
    data = np.empty(int(offsets[-1]), dtype=np.uint8)
    windows = np.lib.stride_tricks.sliding_window_view(pool, hi)
    within = np.arange(hi)
    for a in range(0, n, block):
        b = min(a + block, n)
        data[offsets[a]:offsets[b]] = windows[starts[a:b]][
            within < lens[a:b, None]]
    return pa.Array.from_buffers(
        pa.string(), n,
        [None, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data)])


# -- columns ------------------------------------------------------------------

def money(cents: np.ndarray, precision: int = 12, scale: int = 2) -> pa.Array:
    """Exact decimal(p,s) from unscaled int64 values: the int64 is the low
    lane of the decimal128, the high lane its sign extension."""
    unscaled = np.ascontiguousarray(cents, dtype=np.int64)
    lanes = np.empty((len(unscaled), 2), dtype=np.int64)
    lanes[:, 0] = unscaled
    lanes[:, 1] = unscaled >> 63
    return pa.Array.from_buffers(pa.decimal128(precision, scale),
                                 len(unscaled), [None, pa.py_buffer(lanes)])


def _int64(a) -> pa.Array:
    return pa.array(np.asarray(a, dtype=np.int64), pa.int64())


def _int32(a) -> pa.Array:
    return pa.array(np.asarray(a, dtype=np.int32), pa.int32())


def _date(a) -> pa.Array:
    return _int32(a).cast(pa.date32())


def _pick(codes: np.ndarray, values: list) -> pa.Array:
    """values[codes] as a plain string column, without a Python loop."""
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32)), pa.array(values)).cast(pa.string())


class _Gen:
    def __init__(self, scale: float, seed: int):
        self.n = row_counts(scale)
        self.seed = int(seed)
        self._raw: Dict[str, np.ndarray] = {}

    def rng(self, key: str) -> np.random.Generator:
        return np.random.default_rng(
            [self.seed, zlib.crc32(key.encode("ascii"))])

    def raw(self, key: str, make: Callable[[np.random.Generator], np.ndarray]
            ) -> np.ndarray:
        """A numpy column kept for the columns derived from it."""
        if key not in self._raw:
            self._raw[key] = make(self.rng(key))
        return self._raw[key]

    def draw(self, key: str, lo: int, hi: int, table: str,
             dtype=np.int64) -> np.ndarray:
        """Uniform whole numbers lo..hi, both ends in, one per row."""
        return self.rng(key).integers(lo, hi + 1, self.n[table], dtype=dtype)

    # -- orders, and what lineitem takes from them ---------------------------
    def o_key(self) -> np.ndarray:
        i = np.arange(self.n["orders"], dtype=np.int64)
        return (i >> 3 << 5) + (i & 7) + 1      # first 8 of every 32 keys

    def o_date(self) -> np.ndarray:
        return self.raw("orders.o_orderdate", lambda r: r.integers(
            STARTDATE, ENDDATE - 151 + 1, self.n["orders"], dtype=np.int32))

    def o_custkey(self) -> np.ndarray:
        # a random key of 1..customers that is not a multiple of 3: the
        # k-th such key is k + (k - 1) // 2
        n_cust = self.n["customer"]
        kth = self.rng("orders.o_custkey").integers(
            1, n_cust - n_cust // 3 + 1, self.n["orders"])
        return kth + (kth - 1) // 2

    def lines(self) -> np.ndarray:
        """Lines of each order: 1..7, a few moved by one so that the sum is
        the table's row count."""
        def make(r):
            count = r.integers(1, 8, self.n["orders"], dtype=np.int64)
            gap = self.n["lineitem"] - int(count.sum())
            room = np.flatnonzero(count < 7 if gap > 0 else count > 1)
            count[r.choice(room, abs(gap), replace=False)] += np.sign(gap)
            return count
        return self.raw("lineitem.lines", make)

    def of_order(self, per_order: np.ndarray) -> np.ndarray:
        return np.repeat(per_order, self.lines())

    # -- lineitem --------------------------------------------------------------
    def l_ship(self) -> np.ndarray:
        return self.raw("lineitem.l_shipdate", lambda r: self.of_order(
            self.o_date()) + r.integers(1, 122, self.n["lineitem"],
                                        dtype=np.int32))

    def l_receipt(self) -> np.ndarray:
        return self.raw("lineitem.l_receiptdate", lambda r: self.l_ship()
                        + r.integers(1, 31, self.n["lineitem"],
                                     dtype=np.int32))

    def l_part(self) -> np.ndarray:
        return self.raw("lineitem.l_partkey", lambda r: r.integers(
            1, self.n["part"] + 1, self.n["lineitem"], dtype=np.int64))

    def l_supp(self) -> np.ndarray:
        part, s = self.l_part(), self.n["supplier"]
        i = self.draw("lineitem.l_suppkey", 0, 3, "lineitem")
        return (part + i * (s // 4 + (part - 1) // s)) % s + 1

    def l_qty(self) -> np.ndarray:
        return self.raw("lineitem.l_quantity", lambda r: r.integers(
            1, 51, self.n["lineitem"], dtype=np.int64))

    def l_price(self) -> np.ndarray:
        part = self.l_part()
        retail = 90000 + (part // 10) % 20001 + 100 * (part % 1000)   # cents
        return self.l_qty() * retail

    def l_returnflag(self) -> pa.Array:
        coin = self.draw("lineitem.l_returnflag", 0, 1, "lineitem")
        return _pick(np.where(self.l_receipt() <= CURRENTDATE, coin, 2),
                     ["R", "A", "N"])

    def o_comment(self) -> pa.Array:
        pool = text_pool(self.rng("text.pool"))
        return text_strings(pool, self.rng("orders.o_comment"),
                            self.n["orders"], 19, 78)


# table -> column -> maker.  A column not named here cannot be asked for; a
# query that reads another brings a generator module of its own
# (configs/<config>.json "generator").
COLUMNS: Dict[str, Dict[str, Callable[[_Gen], pa.Array]]] = {
    "region": {
        "r_regionkey": lambda g: _int64(range(5)),
        "r_name": lambda g: pa.array(REGIONS),
    },
    "nation": {
        "n_nationkey": lambda g: _int64(range(25)),
        "n_name": lambda g: pa.array(NATIONS),
        "n_regionkey": lambda g: _int64(REGION_OF),
    },
    "customer": {
        "c_custkey": lambda g: _int64(np.arange(1, g.n["customer"] + 1)),
        "c_nationkey": lambda g: _int64(g.draw(
            "customer.c_nationkey", 0, 24, "customer")),
        "c_mktsegment": lambda g: _pick(g.draw(
            "customer.c_mktsegment", 0, 4, "customer"), SEGMENTS),
    },
    "supplier": {
        "s_suppkey": lambda g: _int64(np.arange(1, g.n["supplier"] + 1)),
        "s_nationkey": lambda g: _int64(g.draw(
            "supplier.s_nationkey", 0, 24, "supplier")),
    },
    "orders": {
        "o_orderkey": lambda g: _int64(g.o_key()),
        "o_custkey": lambda g: _int64(g.o_custkey()),
        "o_orderdate": lambda g: _date(g.o_date()),
        "o_shippriority": lambda g: _int32(np.zeros(g.n["orders"], np.int32)),
        "o_comment": _Gen.o_comment,
    },
    "lineitem": {
        "l_orderkey": lambda g: _int64(g.of_order(g.o_key())),
        "l_suppkey": lambda g: _int64(g.l_supp()),
        "l_quantity": lambda g: money(100 * g.l_qty()),
        "l_extendedprice": lambda g: money(g.l_price()),
        "l_discount": lambda g: money(g.draw(
            "lineitem.l_discount", 0, 10, "lineitem")),
        "l_tax": lambda g: money(g.draw("lineitem.l_tax", 0, 8, "lineitem")),
        "l_returnflag": _Gen.l_returnflag,
        "l_linestatus": lambda g: _pick(
            (g.l_ship() <= CURRENTDATE).astype(np.int32), ["O", "F"]),
        "l_shipdate": lambda g: _date(g.l_ship()),
    },
}


def gen_tables(scale: float, seed: int,
               columns: Mapping[str, Iterable[str]]) -> Dict[str, pa.Table]:
    """The asked-for columns of the asked-for tables, in the order asked."""
    g = _Gen(scale, seed)
    out = {}
    for table, names in columns.items():
        if table not in COLUMNS:
            raise KeyError(f"tpch_gen has no table {table!r}")
        made = {}
        for name in names:
            if name not in COLUMNS[table]:
                raise KeyError(f"tpch_gen has no column {table}.{name}")
            made[name] = COLUMNS[table][name](g)
        out[table] = pa.table(made)
    return out
