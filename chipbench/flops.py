"""Work of one training step, counted from the members' real widths.

Padding units are not counted, so the count is the same whatever layout or
kernel implements the step.  A projection of ``a`` inputs to ``b`` outputs
over a batch of ``B`` rows takes ``2·B·a·b`` operations forward,
``2·B·a·b`` for its weight gradient, and ``2·B·a·b`` for its input
gradient, which the input layer does not need.  The minimal float32 bytes
are those a pass must read and write at least once: forward reads the
input, weight and bias and writes the output; backward reads the output
gradient, input and weight and writes the weight, bias and input
gradients.  Parts: ``input`` (features to first hidden layer), ``mid``
(hidden to hidden) and ``head`` (last hidden layer to classes).
"""
from __future__ import annotations

F32 = 4


def step_counts(members: list, n_features: int, n_classes: int,
                batch: int) -> dict:
    """``{part: {"fwd"|"bwd": {"flops": n, "bytes": n}}}`` per step."""
    out = {p: {d: {"flops": 0, "bytes": 0} for d in ("fwd", "bwd")}
           for p in ("input", "mid", "head")}
    for widths, _act in members:
        dims = (n_features,) + tuple(widths) + (n_classes,)
        last = len(dims) - 2
        for j in range(len(dims) - 1):
            a, b = dims[j], dims[j + 1]
            part = "input" if j == 0 else "head" if j == last else "mid"
            fwd, bwd = out[part]["fwd"], out[part]["bwd"]
            fwd["flops"] += 2 * batch * a * b
            fwd["bytes"] += F32 * (batch * a + a * b + b + batch * b)
            dx = j > 0
            bwd["flops"] += 2 * batch * a * b * (2 if dx else 1)
            bwd["bytes"] += F32 * (batch * b + batch * a + a * b + a * b + b
                                   + (batch * a if dx else 0))
    return out


def step_flops(members: list, n_features: int, n_classes: int,
               batch: int) -> int:
    """Operations that one step's forward and backward passes require."""
    c = step_counts(members, n_features, n_classes, batch)
    return sum(c[p][d]["flops"] for p in c for d in c[p])
