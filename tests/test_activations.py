"""Per-member activation application: the sliced pass, the masked oracle and
the fused kernels' epilogue table agree, and each activation matches its
torch-default definition on known points."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.activations import (ACTIVATIONS, ACTIVATION_ORDER, PAPER_TEN,
                                    apply_activations_masked,
                                    apply_activations_sliced)
from repro.core.population import Population
from repro.kernels.epilogue import VAL_BRANCHES


def test_paper_has_ten():
    assert len(PAPER_TEN) == 10
    assert set(PAPER_TEN) == set(ACTIVATIONS)


def test_known_values():
    x = jnp.asarray([-1.0, 0.0, 2.0])
    np.testing.assert_allclose(ACTIVATIONS["identity"](x), [-1, 0, 2])
    np.testing.assert_allclose(ACTIVATIONS["relu"](x), [0, 0, 2])
    np.testing.assert_allclose(ACTIVATIONS["hardshrink"](x), [-1, 0, 2])
    np.testing.assert_allclose(ACTIVATIONS["hardshrink"](
        jnp.asarray([0.4, -0.5, 0.6])), [0, 0, 0.6])
    np.testing.assert_allclose(ACTIVATIONS["leaky_relu"](x),
                               [-0.01, 0, 2], rtol=1e-6)
    np.testing.assert_allclose(ACTIVATIONS["sigmoid"](jnp.zeros(1)), [0.5])
    # mish(0)=0, gelu(0)=0, tanh(0)=0
    for n in ("mish", "gelu", "tanh", "elu", "selu"):
        np.testing.assert_allclose(float(ACTIVATIONS[n](jnp.zeros(1))[0]),
                                   0.0, atol=1e-7)


@st.composite
def pops(draw):
    n = draw(st.integers(1, 8))
    sizes = draw(st.lists(st.integers(1, 20), min_size=n, max_size=n))
    acts = draw(st.lists(st.sampled_from(sorted(PAPER_TEN)),
                         min_size=n, max_size=n))
    return Population(4, 2, tuple(sizes), tuple(acts), block=8)


@given(pops(), st.booleans())
@settings(max_examples=30, deadline=None)
def test_strategies_agree(pop, sort):
    if sort:
        pop = pop.sorted()
    h = jax.random.normal(jax.random.PRNGKey(pop.num_members),
                          (5, pop.total_hidden))
    a = apply_activations_sliced(h, pop.act_runs)
    b = apply_activations_masked(h, pop.act_ids)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-6)
    # the fused kernels' epilogue: one branch of the lax.switch table per
    # block, picked by the block's activation id, then the padding mask
    blocks = jnp.split(h, len(pop.block_act_ids), axis=-1)
    c = jnp.concatenate([jax.lax.switch(int(i), VAL_BRANCHES, hb)
                         for i, hb in zip(pop.block_act_ids, blocks)], -1)
    c = c * jnp.asarray(pop.hidden_mask)
    want = np.asarray(b) * np.asarray(pop.hidden_mask)
    np.testing.assert_allclose(np.asarray(c), want, rtol=1e-6, atol=1e-6)


def test_activation_order_is_canonical():
    assert list(ACTIVATION_ORDER) == sorted(ACTIVATIONS)
