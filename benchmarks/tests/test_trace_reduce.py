import json
import os

import pytest

from harness import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")
SAMPLE = os.path.join(DATA, "q6_12.xplane.pb.gz")


def test_busy_is_the_union_of_overlapping_intervals():
    busy = T.union([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40)])
    assert busy == [(0, 15), (20, 31)]
    assert T.length(busy) == 26
    assert T.gaps(busy, -5, 40) == [(-5, 0), (15, 20), (31, 40)]
    assert T.length(T.clip(busy, 10, 25)) == 10


def test_self_times_add_up_to_the_busy_time():
    events = [("while.1", 0, 100), ("fusion.2", 10, 30), ("sort.3", 20, 25),
              ("fusion.4", 90, 120), ("copy.5", 200, 210)]
    times = T.self_times(events)
    assert times == {"while.1": 70, "fusion.2": 15, "sort.3": 5,
                     "fusion.4": 30, "copy.5": 10}
    assert sum(times.values()) == T.length(
        T.union((s, e) for _n, s, e in events))


def test_reduce_on_synthetic_intervals():
    ns = 1e6                                     # 1 ms
    ops = [{"ops": [("%fusion.1 = f32[8]{0} fusion(%p.1)", 10 * ns, 20 * ns),
                    ("sort.2", 15 * ns, 40 * ns),
                    ("fusion.3", 60 * ns, 70 * ns),
                    ("fusion.9", 500 * ns, 600 * ns)],  # outside the slice
            "async": [("copy-start.1", 38 * ns, 40 * ns)]}]
    notes = [("collect:q3", 0, 50 * ns), ("collect:q5", 55 * ns, 80 * ns),
             ("something else", 0, 1000 * ns)]
    out = T.reduce(ops, notes)
    assert out["window_s"] == pytest.approx(0.080)
    assert out["busy_s"] == pytest.approx(0.040)
    assert out["device_idle_pct"] == pytest.approx(50.0)
    assert out["queries"] == {"q3": 1, "q5": 1}
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"sort": 0.025, "fusion": 0.015})
    assert dict(map(tuple, out["idle_gaps"])) == pytest.approx(
        {"collect:q3#lead": 0.010, "collect:q3#tail": 0.010,
         T.BETWEEN: 0.005, "collect:q5#lead": 0.005,
         "collect:q5#tail": 0.010})
    # two chips: busy is the mean over the chips, each chip's own union
    two = T.reduce(ops + [{"ops": [("fusion.1", 0, 80 * ns)]}], notes)
    assert two["busy_s"] == pytest.approx(0.060)
    assert two["device_idle_pct"] == pytest.approx(25.0)


def test_nothing_to_read_gives_nothing():
    assert T.reduce([], [("collect:q6", 0, 10)]) is None
    assert T.reduce([{"ops": [("fusion", 0, 5)]}], []) is None
    assert T.reduce([{"ops": [("fusion", 20, 25)]}],
                    [("collect:q6", 0, 10)]) is None
    assert T.reduce([{"ops": []}], [("collect:q6", 0, 10)]) is None


def test_sample_recorded_on_the_chip():
    """A dozen q6 collects traced on the TPU v5e (PR 24, gzipped: 2.5 MB
    raw): the device plane and its op lines are found by name, the
    annotations on the host plane, and the reduction reads what the by-hand
    look read (PERF.md section 5)."""
    if not os.path.isfile(SAMPLE):
        pytest.skip("no chip sample under benchmarks/tests/data")
    lines, notes, seen = T.load(SAMPLE)
    assert "/device:TPU:0" in seen and len(lines) == 1
    assert {T.OPS_LINE, T.ASYNC_LINE, "XLA Modules"} <= set(
        seen["/device:TPU:0"])
    assert len(notes) == 12 and all(n == "collect:q6" for n, _s, _e in notes)
    with open(os.path.join(DATA, "q6_12.expected.json")) as f:
        want = json.load(f)
    out = T.reduce(lines, notes)
    assert out["queries"] == want["queries"] == {"q6": 12}
    for key in ("window_s", "busy_s", "device_idle_pct"):
        assert out[key] == pytest.approx(want[key], rel=1e-9)
    # one program run a query ("XLA Modules" jit_run): busy is their sum
    runs = T.length(T.union((s, e) for n, s, e in
                            seen["/device:TPU:0"]["XLA Modules"]))
    assert out["busy_s"] * 1e9 == pytest.approx(runs, rel=0.01)
    assert sum(s for _n, s in out["device_ops"]) <= out["busy_s"] * (1 + 1e-9)
    assert out["device_ops"][0][0] == "convert_reduce_fusion.kLoop"
    labels = dict(map(tuple, out["idle_gaps"]))
    assert labels["collect:q6#lead"] > labels["collect:q6#tail"] > \
        labels["between-collects"]
    assert sum(labels.values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-6)
