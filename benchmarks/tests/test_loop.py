import pytest

from harness.loops import closed_round_robin as L


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def test_the_query_in_flight_at_the_bell_completes_and_counts():
    clock = FakeClock()

    def takes(seconds):
        def call():
            clock.now += seconds
            return seconds
        return call
    window = L.run([("a", takes(0.4)), ("b", takes(0.7))], seconds=2.0,
                   clock=clock)
    # a b a b: the fourth call starts at 1.5 s < 2.0 s and ends at 2.2 s
    assert [r.query for r in window.records] == ["a", "b", "a", "b"]
    assert window.seconds == pytest.approx(2.2)
    assert L.end_to_end(window) == {"query_ms": pytest.approx(550.0),
                                    "query_p95_ms": pytest.approx(700.0)}


def test_a_call_that_raises_is_a_record_not_the_end():
    def boom():
        raise RuntimeError("no")
    window = L.run([("a", boom), ("b", lambda: 1)], count=4,
                   inspect=lambda q: {"q": q})
    assert [r.error is None for r in window.records] == [False, True] * 2
    assert window.records[1].metrics == {"q": "b"}


def test_p95_of_a_known_list():
    assert L.percentile(list(range(1, 101)), 0.95) == 95
    assert L.percentile([5.0], 0.95) == 5.0
    assert L.percentile(list(range(1, 21)), 0.95) == 19
    assert L.percentile([3, 1, 2], 0.95) == 3
