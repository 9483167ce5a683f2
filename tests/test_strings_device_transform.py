"""Device byte transforms for high-cardinality strings (VERDICT r2 #5).

Correctness: the device packed-range kernels must agree exactly with the
per-entry python loop (the host oracle) over fuzzed unicode-ish data.
Performance is measured on the real chip by scripts in the bench flow;
here a coarse wall-clock ratio guards the O(unique)-python regression."""
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.ops.strings import transform_dict_device
from spark_rapids_tpu.session import DataFrame, TpuSession, col


def _fuzz_strings(n, seed=0, unicode_frac=0.05):
    rng = np.random.default_rng(seed)
    out = []
    pool = "abcdefXYZ 0123456789  \t"
    upool = "äßÆπλ日本語"
    for i in range(n):
        ln = int(rng.integers(0, 24))
        s = "".join(rng.choice(list(pool), ln))
        if rng.random() < unicode_frac and ln:
            pos = int(rng.integers(0, ln))
            s = s[:pos] + str(rng.choice(list(upool))) + s[pos:]
        # guarantee uniqueness (near-unique high-cardinality shape)
        out.append(f"{s}#{i}" if rng.random() < 0.9 else s)
    return out


@pytest.mark.parametrize("kind,args,py", [
    ("upper", (), lambda s: s.upper()),
    ("lower", (), lambda s: s.lower()),
    ("trim", (), lambda s: s.strip()),
    ("ltrim", (), lambda s: s.lstrip()),
    ("rtrim", (), lambda s: s.rstrip()),
    ("substr", (2, 5), lambda s: s[1:6]),
    ("substr", (-4, None), lambda s: s[-4:] if len(s) >= 4 else s),
    ("substr", (0, 3), lambda s: s[0:3]),
])
def test_device_transform_matches_python(kind, args, py):
    vals = _fuzz_strings(3000) + ["", " ", "  a  ", None, "ÄÖÜ  ",
                                  "日本語abc"]
    d = pa.array(vals, pa.string())
    got = transform_dict_device(d, kind, args).to_pylist()
    exp = [None if v is None else py(v) for v in vals]
    assert got == exp


def test_session_transform_uses_device_path_and_matches():
    vals = _fuzz_strings(20000, seed=3)
    tbl = pa.table({"s": pa.array(vals, pa.string())})
    dev = TpuSession({
        "spark.rapids.tpu.sql.string.transformDeviceMinUnique": 1000})
    host = TpuSession({
        "spark.rapids.tpu.sql.string.transformDeviceMinUnique": 10**9})
    from spark_rapids_tpu.plan.strings import Substring, Upper
    df = dev.from_arrow(tbl).select(Upper(col("s")),
                                    Substring(col("s"), 2, 6),
                                    names=["u", "sub"])
    a = df.collect()
    b = DataFrame(df._plan, host).collect()
    assert a.to_pydict() == b.to_pydict()


def test_byte_tensor_extraction_zero_copy_fast():
    """dict_byte_tensors must be vectorized buffer reads, not a per-entry
    python join (the round-2 finding): 500k entries in well under a
    second, exact against a python rebuild."""
    from spark_rapids_tpu.ops.strings import dict_byte_tensors
    vals = _fuzz_strings(500_000, seed=7, unicode_frac=0.01)
    d = pa.array(vals, pa.string())
    t0 = time.perf_counter()
    offs, bytes_ = dict_byte_tensors(d)
    took = time.perf_counter() - t0
    assert took < 1.0, took
    joined = "".join(v or "" for v in vals).encode("utf-8")
    n = len(vals)
    assert bytes_[:len(joined)].tobytes() == joined
    lens = [len((v or "").encode("utf-8")) for v in vals]
    assert offs[:n + 1].tolist() == list(np.cumsum([0] + lens))


def test_device_transform_correct_at_scale():
    """200k near-unique strings through the packed-range kernel match the
    python oracle exactly (perf is the bench flow's; tier-1 runs on the
    CPU backend, so only correctness is asserted here)."""
    vals = _fuzz_strings(200_000, seed=7, unicode_frac=0.0)
    d = pa.array(vals, pa.string())
    out_dev = transform_dict_device(d, "upper", ())
    assert out_dev.to_pylist() == [v.upper() for v in vals]
