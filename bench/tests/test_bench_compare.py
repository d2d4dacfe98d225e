"""The comparison that decides ``correct``, on hand-made arenas."""
import numpy as np

from bench.reference import ps as ref_ps


def test_support_overlap_and_value_ratio():
    ref = np.array([0, 2, 0, 4, 1, 0], np.float32)
    prog = np.array([0, 2, 3, 8, 0, 0], np.float32)
    overlap, ratio = ref_ps.supports(prog, ref, [3, 3])
    # leaf 0: the one reference entry is kept; leaf 1: one of two
    assert overlap == [1.0, 0.5]
    assert ratio == [1.0, 2.0]


def test_leaves_that_round_off_moves_are_left_out_by_rule():
    sizes = [2, 2, 2]
    # leaf 2's first-batch update is a millionth of the median leaf's:
    # its gap is not counted, whatever its name
    m1 = np.array([3, 4, 6, 8, 3e-6, 4e-6], np.float32)
    ref = {"sizes": sizes, "M1": m1, "change": m1, "losses": np.ones(3),
           "n1": 2, "bytes": 10}
    prog = dict(ref, M1=m1 * np.array([1, 1, 1, 1, 9, 9], np.float32),
                change=m1 * 1.1)
    got = ref_ps.compare(prog, ref)
    assert got["grad1_gap"] == 0.0
    # the change gap is the worst moving leaf's, 10%
    assert abs(got["change_gap"] - 0.1) < 1e-6
    assert got["loss_gap"] == 0.0 and got["bytes_gap"] == 0.0
