"""The serving driver's timing of requests, by hand: which dispatch
finished each request, from the program's own done stamps."""

import numpy as np
import pytest

from bench.drivers.serve_continuous import (Call, arrivals, attribute,
                                            dispatch_work)


def _calls(spans):
    """Calls made and ready at the given wall times (the host clock the
    same, shifted)."""
    return [Call("decode", a - 50.0, b - 50.0, a, b) for a, b in spans]


# three dispatches: [1.00, 1.30], [1.31, 1.50], [1.52, 1.90]
SPANS = [(1.00, 1.30), (1.31, 1.50), (1.52, 1.90)]


def test_attribute_places_each_done_stamp_after_one_dispatch():
    origin = 0.9503          # the program's start, unknown to the driver
    stamps = [1.3004 - origin, 1.3004 - origin, 1.5101 - origin,
              1.9002 - origin]
    start, index = attribute(_calls(SPANS), stamps, wall_lo=0.94,
                             wall_hi=0.96, wall_end=1.95)
    assert index == [0, 0, 1, 2]
    assert 0.94 <= start <= origin + 1e-9


def test_attribute_narrows_the_start_from_many_stamps():
    """One stamp leaves several starts open; together they leave one."""
    rng = np.random.default_rng(0)
    t, spans = 1.0, []
    for _ in range(30):
        d = rng.uniform(0.05, 0.4)
        spans.append((t, t + d))
        t += d + 0.01
    origin, picks = 0.953, list(range(0, 30, 4))
    stamps = [spans[k][1] + 0.004 - origin for k in picks]
    start, index = attribute(_calls(spans), stamps, wall_lo=0.80,
                             wall_hi=1.10, wall_end=t + 1)
    assert index == picks
    assert abs(start - origin) < 0.01


def test_attribute_refuses_a_stamp_inside_a_dispatch():
    stamps = [1.3004 - 0.95, 1.40 - 0.95]     # the second while busy
    with pytest.raises(ValueError):
        attribute(_calls(SPANS), stamps, wall_lo=0.95, wall_hi=0.95,
                  wall_end=1.95)


def test_dispatch_work_counts_admissions_steps_and_kv():
    calls = [Call("admit_decode", 0, 1, 0, 1,
                  (np.array([True, False]), np.array([2, 0]))),
             Call("decode", 1, 2, 1, 2, (None, np.array([1, 0])))]
    work = dispatch_work(calls, prompt_len=4, slots=2)
    assert [w["kind"] for w in work] == ["admit", "decode"]
    assert [(w["steps"], w["kv"], w["admitted"], w["decoded"])
            for w in work] == [(2, 5 + 6, 1, 2), (1, 7, 0, 1)]
    calls[1].work = None
    assert dispatch_work(calls, prompt_len=4, slots=2) is None


def test_arrivals_are_a_fixed_poisson_schedule():
    a = arrivals(1000, 4.0, 1)
    np.testing.assert_array_equal(a, arrivals(1000, 4.0, 1))
    assert np.all(np.diff(a) > 0) and abs(a[-1] / 1000 - 0.25) < 0.03
