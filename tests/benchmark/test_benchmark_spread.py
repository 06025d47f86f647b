"""``benchmark/spread.py`` on hand-written lists: the distance between
the quartiles with the run farthest from the median left out where that
narrows it, the bound a widest spread asks for, and the table of a
directory of result lines."""

import json
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import spread as sp  # noqa: E402


@pytest.mark.parametrize("values,whole,expected", [
    # six runs that agree
    ([3970.1] * 6, 0.0, 0.0),
    # one disturbed run among six: exclusive quartiles of the six are
    # 100.75 and 115.5; of the five that are left 100.5 and 103.5
    ([100, 101, 102, 103, 104, 150], 14.75, 3.0),
    # the same run on the low side, and not the last of the list
    ([104, 54, 103, 102, 101, 100], 14.75, 3.0),
    # tbig_train's two levels, a whole step more or less in the window:
    # one run on the other level is left out, three and three are not
    ([67062, 67062, 67072, 67062, 67062, 67062], 2.5, 0.0),
    ([67062, 67072, 67062, 67072, 67062, 67072], 10.0, 10.0),
    # an even and an odd count with nothing disturbed: the farthest run
    # still goes where that narrows the distance
    ([1, 2, 3, 4, 5, 6], 3.5, 3.0),
    ([1, 2, 3, 4, 5], 3.0, 2.5),
    # too few runs to leave one out
    ([5.0, 7.0], 3.0, 3.0),
    ([5.0], 0.0, 0.0),
])
def test_spread_on_hand_written_lists(values, whole, expected):
    assert sp.quartile_distance(values) == pytest.approx(whole)
    assert sp.spread(values) == pytest.approx(expected)


def test_leaving_a_run_out_never_widens_and_order_does_not_matter():
    rng = random.Random(36)
    for _ in range(200):
        values = [rng.gauss(100, 3) for _ in range(rng.randint(3, 9))]
        assert sp.spread(values) <= sp.quartile_distance(values)
        assert sp.spread(sorted(values)) == pytest.approx(sp.spread(values))


def test_a_share_is_of_the_whole_sets_median():
    values = [100, 101, 102, 103, 104, 150]
    assert sp.spread_share(values) == pytest.approx(3.0 / 102.5)
    # a count that reads 0 in every run, or in most
    assert sp.spread_share([0.0] * 6) == 0.0
    assert sp.spread_share([0, 0, 0, 0, 6, 7]) == float("inf")


@pytest.mark.parametrize("widest,bound", [
    (0.0000052, 0.01),   # never under 1%
    (0.0024, 0.01),
    (0.0025, 0.015),     # five times it, to the nearest half per cent
    (0.0046, 0.025),
    (0.0082, 0.04),
    (0.0117, 0.06),
    (0.0149, 0.075),
    (0.0207, 0.1),       # never over 10%
    (0.05, 0.1),
])
def test_the_bound_a_widest_spread_asks_for(widest, bound):
    assert sp.bound_for(widest) == pytest.approx(bound)


def test_table_of_a_directory_of_result_lines(tmp_path):
    for i, v in enumerate([100, 101, 102, 103, 104, 150]):
        line = {"correct": True, "attempted": 9, "failed": 0, "metrics": {
            "rate": {"value": v, "unit": "x"},
            "setup_s": {"value": 30.0, "unit": "s"}}, "device": {}}
        (tmp_path / f"run{i}.out").write_text(
            json.dumps({"notes": {}}) + "\n" + json.dumps(line) + "\n")
    (tmp_path / "run0.err").write_text("a warning, no result line\n")
    (tmp_path / "empty.out").write_text("")
    (tmp_path / "sub").mkdir()
    runs = sp.result_lines(str(tmp_path))
    assert len(runs) == 6
    rate, setup = sp.table(runs, {"rate": 0.01})
    assert rate["metric"] == "rate" and rate["n"] == 6
    assert rate["median"] == 102.5 and rate["range"] == [100, 150]
    assert rate["spread"] == pytest.approx(3.0)
    assert rate["spread_over_bound"] == pytest.approx(3.0 / 102.5 / 0.01)
    assert setup["spread"] == 0.0 and setup["spread_over_bound"] is None
    assert sp.main([str(tmp_path)]) == 0 and sp.main([]) == 2
