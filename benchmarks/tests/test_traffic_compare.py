import random

import numpy as np
import pandas as pd
import pytest

from harness import compare, registry

CLASSES = ["q1", "q3", "q5", "q6"]


def rounds(kind, seed, n, **params):
    s = registry.load_module("traffic", kind).schedule(
        params, CLASSES, random.Random(seed))
    return [[next(sess) for _ in range(n)] for sess in s]


@pytest.mark.parametrize("kind,per_round", [("closed_seq", 1),
                                            ("closed_burst", 4)])
def test_same_work_for_every_seed_in_another_order(kind, per_round):
    big = 3000000017                   # more than 32 signed bits hold
    a = rounds(kind, big, 8)[0]
    assert a == rounds(kind, big, 8)[0]            # same seed, same inputs
    b = rounds(kind, 7, 8)[0]
    assert a != b
    for sched in (a, b):
        assert all(r["due_s"] is None for r in sched)       # closed loop
        assert all(len(r["sends"]) == per_round for r in sched)
        sends = [c for r in sched for c in r["sends"]]
        for i in range(0, len(sends), 4):          # every cycle: each class
            assert sorted(sends[i:i + 4]) == CLASSES


def test_sessions_parameter():
    assert len(rounds("closed_seq", 1, 1, sessions=3)) == 3


def test_check_frames():
    want = pd.DataFrame({"k": ["a", "b"], "n": np.array([1, 2]),
                         "d": pd.to_datetime(["1995-03-15", "1996-01-01"]),
                         "x": [1.0, 2.0], "hll": [100, 200]})
    got = pd.DataFrame({"k": ["b", "a"], "n": [2, 1],
                        "d": ["1996-01-01T00:00:00", "1995-03-15"],
                        "x": [2, 1.0000005], "hll": [204, 97]})
    worst = compare.check_frames("t", got, want, approx=("hll",))
    assert worst == pytest.approx(5e-7)
    with pytest.raises(compare.Mismatch):
        compare.check_frames("t", got, want, approx=("hll",), rtol=1e-7)
    with pytest.raises(compare.Mismatch):
        compare.check_frames("t", got, want)        # hll held exact
    with pytest.raises(compare.Mismatch):
        compare.check_frames("t", got.assign(n=[2, 2]), want,
                             approx=("hll",))
    with pytest.raises(compare.Mismatch):
        compare.check_frames("t", got.iloc[:1], want, approx=("hll",))
