"""ParallelMLP — the paper's fused population-of-MLPs as a JAX module.

Parameters (one fused set for the whole population of P members):
    w1 : (total_hidden, in_features)   — concatenated input→hidden weights
    b1 : (total_hidden,)
    w2 : (out_features, total_hidden)  — fused hidden→output weights (M3 operand)
    b2 : (P, out_features)

The forward pass is the paper's four steps (§3): matmul → segmented activation
→ M3.  ``loss_fn`` returns *per-member* losses; the fused scalar objective is
their SUM so that d(loss)/d(member-m-params) equals the gradient member m
would see if trained alone — the independence property tested in
tests/test_independence.py.

Init matches torch.nn.Linear defaults (U(±1/√fan_in)) with *per-member*
fan-in for the output layer, so every member initialises exactly as it would
standalone.
"""
from __future__ import annotations

from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.m3 import m3 as _m3_apply
from repro.core.activations import apply_activations_masked, apply_activations_sliced
from repro.core.population import Population

Task = Literal["classification", "regression"]


def init_params(key: jax.Array, pop: Population, dtype=jnp.float32) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    ht, fi, fo = pop.total_hidden, pop.in_features, pop.out_features
    bound1 = 1.0 / np.sqrt(fi)
    w1 = jax.random.uniform(k1, (ht, fi), dtype, -bound1, bound1)
    b1 = jax.random.uniform(k2, (ht,), dtype, -bound1, bound1)
    # per-member output fan-in: member's true hidden size
    bound2 = (1.0 / jnp.sqrt(jnp.asarray(pop.member_fan_in, dtype)))  # (ht,)
    w2 = jax.random.uniform(k3, (fo, ht), dtype, -1.0, 1.0) * bound2[None, :]
    bound2_m = 1.0 / jnp.sqrt(jnp.asarray(np.array(pop.hidden_sizes, np.float32), dtype))
    b2 = jax.random.uniform(k4, (pop.num_members, fo), dtype, -1.0, 1.0) * bound2_m[:, None]
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2}


def forward(params: dict, x: jax.Array, pop: Population, *,
            act_impl: str = "sliced") -> jax.Array:
    """x (B, in) → logits (B, P, out).  The paper's steps 1–4.
    ``act_impl`` picks the activation pass: ``sliced`` (one pass per
    contiguous activation run) or ``masked`` (branchless select over all
    ten, for layouts not sorted by activation)."""
    h = x @ params["w1"].T + params["b1"]                     # 1. fused matmul
    if act_impl == "sliced":
        h = apply_activations_sliced(h, pop.act_runs)          # 2. per-member act
    elif act_impl == "masked":
        h = apply_activations_masked(h, pop.act_ids)
    else:
        raise ValueError(f"unknown act_impl {act_impl!r}")
    h = h * jnp.asarray(pop.hidden_mask, h.dtype)              # kill padding units
    y = _m3_apply(h, params["w2"], pop)                        # 3+4. M3
    return y + params["b2"][None, :, :]


def member_losses(logits: jax.Array, targets: jax.Array, task: Task) -> jax.Array:
    """(B, P, O) × (B,) or (B, O) → per-member mean loss (P,)."""
    if task == "classification":
        logp = jax.nn.log_softmax(logits, axis=-1)             # (B, P, O)
        nll = -jnp.take_along_axis(
            logp, targets[:, None, None].astype(jnp.int32), axis=-1)[..., 0]
        return nll.mean(axis=0)                                # (P,)
    elif task == "regression":
        err = logits - targets[:, None, :]                     # broadcast over P
        return (err ** 2).mean(axis=(0, 2))
    raise ValueError(task)


def fused_loss(params, x, targets, pop: Population, task: Task = "classification",
               **fw) -> tuple[jax.Array, jax.Array]:
    """Scalar objective = SUM of member losses (keeps gradients independent
    and identical to standalone training).  Returns (scalar, per_member)."""
    per = member_losses(forward(params, x, pop, **fw), targets, task)
    return per.sum(), per


def member_accuracy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    pred = jnp.argmax(logits, axis=-1)                         # (B, P)
    return (pred == targets[:, None]).mean(axis=0)             # (P,)


# ---------------------------------------------------------------------- #
# plain SGD training step (the paper trains with vanilla backprop); the   #
# full framework optimisers live in repro/optim and are reused by         #
# examples/quickstart.py — this compact step keeps the core standalone.   #
# ---------------------------------------------------------------------- #

@partial(jax.jit, static_argnames=("pop", "task", "act_impl"))
def sgd_step(params, x, targets, lr, pop: Population,
             task: Task = "classification", *, act_impl: str = "sliced"):
    """One fused SGD step over the whole population.

    ``lr`` may be a scalar (paper) or a per-member vector (P,) — the paper's
    §7 "parallelise the learning rate too", free under this layout because
    every parameter belongs to exactly one member.
    """
    (loss, per), grads = jax.value_and_grad(fused_loss, has_aux=True)(
        params, x, targets, pop, task, act_impl=act_impl)
    lr = jnp.asarray(lr)
    if lr.ndim == 0:
        scale = {"w1": lr, "b1": lr, "w2": lr, "b2": lr}
    else:  # per-member lr vector → expand along the fused axes
        per_unit = lr[jnp.asarray(pop.segment_ids)]            # (ht,)
        scale = {"w1": per_unit[:, None], "b1": per_unit,
                 "w2": per_unit[None, :], "b2": lr[:, None]}
    new = {k: params[k] - scale[k] * grads[k] for k in params}
    return new, loss, per


def extract_member(params: dict, pop: Population, m: int) -> dict:
    """Pull member m's standalone MLP out of the fused parameters."""
    sl = pop.member_slice(m)
    return {"w1": params["w1"][sl], "b1": params["b1"][sl],
            "w2": params["w2"][:, sl], "b2": params["b2"][m],
            "activation": pop.activations[m]}


def member_forward(member: dict, x: jax.Array) -> jax.Array:
    """Standalone forward of one extracted member (the sequential baseline)."""
    from repro.core.activations import ACTIVATIONS
    h = ACTIVATIONS[member["activation"]](x @ member["w1"].T + member["b1"])
    return h @ member["w2"].T + member["b2"]
