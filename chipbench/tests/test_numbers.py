"""The compared numbers on hand-made readings: the members' 10th largest
gap passes a few members that step across a jump, and fails a leaf that
did not move."""
import numpy as np

import reference


def _readings(n=40, seed=0):
    rng = np.random.default_rng(seed)
    sums = {"w_in": rng.uniform(1, 2, n), "b_in": rng.uniform(0.1, 0.2, n),
            "w_out": rng.uniform(1, 2, n), "b_out": rng.uniform(0.1, 0.2, n)}
    pers = rng.uniform(0.5, 0.7, (8, n))
    ref = {"pers": pers, "grad1": sums, "change": sums, "moment": sums}
    prog = {"losses": pers.sum(axis=1), "pers": pers.copy(),
            "change": dict(sums), "moment": dict(sums)}
    return prog, ref


def _numbers(prog, ref):
    chip_of = np.zeros(len(ref["pers"][0]), np.int64)
    return reference.numbers(prog, ref, chip_of, 1, adam=True)


def test_agreeing_readings_read_zero():
    prog, ref = _readings()
    out = _numbers(prog, ref)
    for k in ("loss", "first_loss", "member_loss_10th", "change",
              "change_member_10th", "moment_member_10th"):
        assert out[k] == 0.0, k


def test_nine_members_off_pass_the_tenth():
    prog, ref = _readings()
    for leaf in ("w_in", "b_out"):
        v = prog["change"][leaf].copy()
        v[:9] *= 1.5
        prog["change"][leaf] = v
    out = _numbers(prog, ref)
    assert out["change_member_10th"] == 0.0
    assert out["change"] > 0.01


def test_a_leaf_left_unmoved_fails_the_tenth():
    prog, ref = _readings()
    prog["change"]["b_in"] = np.zeros_like(prog["change"]["b_in"])
    out = _numbers(prog, ref)
    assert out["change_member_10th"] > 0.5
