"""The reference, the control and the origin of each answer."""
import numpy as np
import pytest

from conftest import CHECKOUT, TINY_ROAD
from harness import graphs
from harness.check import duplicates
from harness.reference import UNREACHABLE, Reference
from harness.traffic import PairSource, rng_for


def test_reference_on_a_hand_graph():
    # 0 -1- 1 -1- 2, and a high-quality detour 0 -3- 3 -3- 4 -3- 2
    e = graphs.EdgeList(6, np.array([0, 1, 0, 3, 4]),
                        np.array([1, 2, 3, 4, 2]),
                        np.array([1.0, 1.0, 3.0, 3.0, 3.0]))
    ref = Reference(e)
    s, t, w = [0, 0, 0, 5, 2, 0], [2, 2, 5, 5, 2, 2], [0, 1, 0, 1, 1, 2]
    np.testing.assert_array_equal(ref.distances(s, t, w),
                                  [2, 3, UNREACHABLE, 0, 0, UNREACHABLE])


def test_control_breaks_the_guarantee_and_the_check_sees_it():
    """The control (one quality level too loose) on a cell's own traffic
    reads many wrong answers; the reference against itself reads none."""
    edges = graphs.make_graph(TINY_ROAD)
    ref = Reference(edges)
    src = PairSource({"pairs": "uniform", "levels": "uniform"},
                     edges.num_nodes, ref.num_levels, 5)
    s, t, w = src.draw(rng_for(5, "window"), 3000)
    exact = ref.distances(s, t, w)
    assert np.count_nonzero(ref.distances(s, t, w) != exact) == 0
    assert np.count_nonzero(ref.control(s, t, w) != exact) > 100


def test_duplicates_are_requests_submitted_while_their_key_was_open():
    s = np.array([1, 2, 1, 2, 3, 1])
    t = np.array([2, 1, 2, 1, 4, 2])
    w = np.array([0, 0, 0, 0, 0, 1])
    submit = np.array([0.0, 1.0, 2.0, 5.0, 6.0, 6.5])
    deliver = np.array([3.0, 3.0, 4.0, 7.0, 6.5, 7.0])
    # 1: key (1,2,0) open since 0 -> dup; 2: still open (0 ends at 3) -> dup;
    # 3: every earlier one answered by 5 -> not; 4, 5: new keys
    np.testing.assert_array_equal(
        duplicates(s, t, w, submit, deliver, 10, 2),
        [False, True, True, False, False, False])
    deliver[0] = np.nan          # never answered: stays open
    assert duplicates(s, t, w, submit, deliver, 10, 2)[3]


@pytest.mark.parametrize("cell", ["road-uniform", "social-zipf"])
def test_control_comes_out_not_correct_through_judge(cell):
    """The control in the program's place, on a cell's own graph and rate,
    judged by the run's own check: every window request answered on time,
    and `correct` reads false. (Two seconds of the window keep the test
    short; bench/control.py reads it at the cell's full window.)"""
    import control
    from harness.spec import Bench
    out = control.control_run(Bench(CHECKOUT).cell(cell), 2**31 + 7, 2.0)
    assert not out["correct"]
    assert out["checks"]["wrong"]["value"] > 100
    assert out["checks"]["lost"]["value"] == 0
