"""The traffic mixes: data files whose pattern is found by name."""
import itertools

import pytest

from chipbench import harness, traffic


def _mix(name):
    return traffic.load(harness.HERE / "traffic" / f"{name}.json")


def test_repeat_warms_up_with_the_operation_it_repeats():
    setup, window = traffic.plan(_mix("cold"))
    assert setup == [{"op": "solve"}]
    assert list(itertools.islice(window, 3)) == [{"op": "solve"}] * 3


def test_budget_schedule_is_the_same_for_every_run():
    mix = _mix("refresh")
    days = mix["days"]
    setup, window = traffic.plan(mix)
    again = traffic.plan(mix)[1]
    assert setup == [{"op": "refresh", "budget_scale": 1.0}]
    first = list(itertools.islice(window, 2 * days))
    assert first == list(itertools.islice(again, 2 * days))
    scales = [op["budget_scale"] for op in first]
    assert scales[:days - 1] == scales[days - 1:2 * days - 2]
    assert all(abs(s - 1.0) <= mix["spread"] for s in scales)
    assert len(set(scales[:days - 1])) == days - 1


def test_unknown_pattern_is_an_error():
    with pytest.raises(ValueError):
        traffic.plan({"pattern": "no_such_pattern"})
