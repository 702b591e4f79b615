"""The per-step kernel-launch budget (launch/launch_count.py, DESIGN.md
§9): the fully fused population path costs exactly 2·(depth+1) Pallas
launches per train step — one per layer per direction — INDEPENDENT of
batch size.  Counted statically off the jaxpr (backend-independent, so the
CI interpret-mode count equals the TPU dispatch count); the scanned train
chunk multiplies the budget by its trip count and nothing else."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.activations import ACTIVATION_ORDER
from repro.core import deep
from repro.core.population import LayeredPopulation
from repro.launch.launch_count import (count_pallas_launches,
                                       fused_step_budget, phase_launches)

_WIDTHS = ((5, 3), (12, 9), (7,), (17, 9, 5), (8, 8),
           (5, 3), (3, 11, 2), (24, 16), (4,), (9, 9, 9))
LP = LayeredPopulation(6, 3, _WIDTHS, ACTIVATION_ORDER, block=8)


def _loss(x, y):
    def loss(p):
        return deep.fused_loss(p, x, y, LP, bd_impl="fused")[0]
    return loss


@pytest.mark.parametrize("b", [9, 1024], ids=["small_b", "large_b"])
def test_fused_step_meets_budget(b):
    """fwd = depth+1 launches, bwd = depth+1 launches, at B=9 AND B=1024:
    the two-level-grid backward keeps the count batch-independent."""
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    x = jnp.zeros((b, LP.in_features))
    y = jnp.zeros((b,), jnp.int32)
    assert phase_launches(_loss(x, y), params) == fused_step_budget(LP.depth)


def test_budget_formula():
    assert fused_step_budget(1) == {"fwd": 2, "bwd": 2, "total": 4}
    assert fused_step_budget(3) == {"fwd": 4, "bwd": 4, "total": 8}


def test_xla_path_launches_nothing():
    """The einsum path is the zero baseline — it proves the counter counts
    pallas_call equations, not ops in general."""
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    x = jnp.zeros((9, LP.in_features))
    y = jnp.zeros((9,), jnp.int32)

    def loss(p):
        return deep.fused_loss(p, x, y, LP, bd_impl="einsum")[0]
    assert phase_launches(loss, params) == {"fwd": 0, "bwd": 0, "total": 0}


def test_scan_chunk_is_budget_times_trip_count():
    """The scanned train chunk (make_population_train_step) is loop-
    weighted: scan_steps × the per-step budget, nothing hidden outside
    the scan body."""
    scan_steps = 4
    params = deep.init_params(jax.random.PRNGKey(0), LP)
    chunk = deep.make_population_train_step(
        LP, bd_impl="fused", scan_steps=scan_steps,
        donate=False)
    xs = jnp.zeros((scan_steps, 9, LP.in_features))
    ys = jnp.zeros((scan_steps, 9), jnp.int32)
    n = count_pallas_launches(chunk, params, xs, ys, 0.05)
    assert n == scan_steps * fused_step_budget(LP.depth)["total"]
