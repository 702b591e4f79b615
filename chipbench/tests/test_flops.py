"""The work counter against a hand count."""
import flops


def test_three_member_layout_by_hand():
    # features 4, classes 3, batch 2; members of widths (5,), (2, 6), (3,)
    members = [((5,), "relu"), ((2, 6), "tanh"), ((3,), "gelu")]
    c = flops.step_counts(members, 4, 3, 2)
    # input: 4->5, 4->2, 4->3; mid: 2->6; head: 5->3, 6->3, 3->3
    assert c["input"]["fwd"]["flops"] == 2 * 2 * (20 + 8 + 12)
    assert c["input"]["bwd"]["flops"] == 2 * 2 * (20 + 8 + 12)
    assert c["mid"]["fwd"]["flops"] == 2 * 2 * 12
    assert c["mid"]["bwd"]["flops"] == 2 * 2 * 2 * 12
    assert c["head"]["fwd"]["flops"] == 2 * 2 * (15 + 18 + 9)
    assert c["head"]["bwd"]["flops"] == 2 * 2 * 2 * (15 + 18 + 9)
    assert flops.step_flops(members, 4, 3, 2) == 4 * (
        2 * 40 + 3 * 12 + 3 * 42)
    # bytes of the mid layer 2->6: forward x(2*2) w(12) b(6) y(2*6);
    # backward dy(12) x(4) w(12) dw(12) db(6) dx(4)
    assert c["mid"]["fwd"]["bytes"] == 4 * (4 + 12 + 6 + 12)
    assert c["mid"]["bwd"]["bytes"] == 4 * (12 + 4 + 12 + 12 + 6 + 4)
    # the input layer writes no input gradient
    assert c["input"]["bwd"]["bytes"] == 4 * sum(
        2 * b + 2 * 4 + 2 * 4 * b + b for b in (5, 2, 3))
