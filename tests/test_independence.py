"""THE paper's correctness property: members of a fused ParallelMLP train
EXACTLY as they would standalone — gradients never mix across members.

Method: init a fused population; extract each member; train the fused
network with SGD for several steps; train each extracted member standalone
on the same batches; the fused member slices must equal the standalone
parameters to float tolerance — on the single-layer engine and on the
layered engine the benchmark runs, through both of its paths.  Also covers
per-member learning rates (paper §7) and loss equality."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Population, deep, extract_member, forward,
                        init_params, member_forward, sgd_step)
from repro.core.activations import ACTIVATIONS
from repro.core.parallel_mlp import member_losses
from repro.core.population import LayeredPopulation

POP = Population(6, 3, (3, 9, 1, 20, 9),
                 ("relu", "tanh", "identity", "mish", "sigmoid"), block=8)
# the same members as a depth-1 layered population
LP = LayeredPopulation(6, 3, tuple((h,) for h in POP.hidden_sizes),
                       POP.activations, block=8)


def _engine(name):
    """(init, step, extract) of the single-layer engine ("bucketed": its
    M3 is the bucketed einsum) or of the layered engine through
    ``bd_impl`` ("einsum", "fused"), with members as w1/b1/w2/b2."""
    if name == "bucketed":
        return (lambda k: init_params(k, POP),
                lambda p, x, y, lr: sgd_step(p, x, y, lr, POP),
                lambda p, m: extract_member(p, POP, m))

    def extract(p, m):
        e = deep.extract_member(p, LP, m)
        return {"w1": e["w_in"], "b1": e["b_in"], "w2": e["w_out"],
                "b2": e["b_out"], "activation": e["activation"]}

    return (lambda k: deep.init_params(k, LP),
            lambda p, x, y, lr: deep.sgd_step(p, x, y, lr, LP, bd_impl=name),
            extract)


def standalone_step(member, x, y, lr):
    """Plain SGD on one extracted MLP (classification NLL, mean over batch)."""
    def loss(m):
        logits = member_forward_dict(m, x, member["activation"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()

    grads = jax.grad(loss)({k: member[k] for k in ("w1", "b1", "w2", "b2")})
    return {k: member[k] - lr * grads[k] if k in grads else member[k]
            for k in member}


def member_forward_dict(m, x, act):
    h = ACTIVATIONS[act](x @ m["w1"].T + m["b1"])
    return h @ m["w2"].T + m["b2"]


@pytest.mark.parametrize("engine", ["bucketed", "einsum", "fused"])
def test_fused_equals_standalone(engine):
    init, step_fn, extract = _engine(engine)
    params = init(jax.random.PRNGKey(42))
    members = [extract(params, m) for m in range(POP.num_members)]

    kx = jax.random.PRNGKey(7)
    lr = 0.05
    fused = params
    for step in range(5):
        kx, k1, k2 = jax.random.split(kx, 3)
        x = jax.random.normal(k1, (16, 6))
        y = jax.random.randint(k2, (16,), 0, 3)
        fused, _, _ = step_fn(fused, x, y, lr)
        members = [standalone_step(m, x, y, lr) for m in members]

    for m in range(POP.num_members):
        got = extract(fused, m)
        want = members[m]
        for k in ("w1", "b1", "w2", "b2"):
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]), rtol=2e-4, atol=2e-5,
                err_msg=f"member {m} param {k} diverged — gradients mixed!")


def test_padding_units_never_update():
    key = jax.random.PRNGKey(0)
    params = init_params(key, POP)
    pad = 1.0 - np.asarray(POP.hidden_mask)
    w1_pad_before = np.asarray(params["w1"]) * pad[:, None]
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 3)
    new, _, _ = sgd_step(params, x, y, 0.1, POP)
    # w2 columns of padding units get zero gradient (h is masked there);
    # w1 rows of padding units receive zero gradient through M3
    np.testing.assert_allclose(
        np.asarray(new["w1"]) * pad[:, None], w1_pad_before, atol=1e-7)


def test_per_member_lr():
    """lr vector: member m trains with its own step size (paper §7)."""
    key = jax.random.PRNGKey(3)
    params = init_params(key, POP)
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 6))
    y = jax.random.randint(jax.random.PRNGKey(5), (8,), 0, 3)
    lrs = jnp.asarray([0.0, 0.1, 0.0, 0.2, 0.05])
    new, _, _ = sgd_step(params, x, y, lrs, POP)
    for m, lr in enumerate(np.asarray(lrs)):
        sl = POP.member_slice(m)
        same = np.allclose(np.asarray(new["w1"][sl]),
                           np.asarray(params["w1"][sl]))
        assert same == (lr == 0.0), (m, lr)


def test_fused_loss_equals_member_losses():
    key = jax.random.PRNGKey(9)
    params = init_params(key, POP)
    x = jax.random.normal(jax.random.PRNGKey(10), (12, 6))
    y = jax.random.randint(jax.random.PRNGKey(11), (12,), 0, 3)
    logits = forward(params, x, POP)
    per = member_losses(logits, y, "classification")
    for m in range(POP.num_members):
        mem = extract_member(params, POP, m)
        lg = member_forward(mem, x)
        logp = jax.nn.log_softmax(lg)
        want = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
        np.testing.assert_allclose(float(per[m]), float(want), rtol=1e-5)
