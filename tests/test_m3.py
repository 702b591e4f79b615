"""M3 semantics: the bucketed M3 agrees (values AND gradients) with a
brute-force per-member loop, across hypothesis-driven layouts/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.m3 import m3
from repro.core.population import Population

ACTS = st.sampled_from(["relu", "tanh", "gelu"])


def brute_force(h, w2, pop):
    """y[b,m,o] = member-m slice matmul — the obvious loop."""
    outs = []
    for m in range(pop.num_members):
        sl = pop.member_slice(m)
        outs.append(h[:, sl] @ w2[:, sl].T)
    return jnp.stack(outs, axis=1)


@st.composite
def layouts(draw):
    n = draw(st.integers(1, 6))
    sizes = draw(st.lists(st.integers(1, 33), min_size=n, max_size=n))
    block = draw(st.sampled_from([1, 8]))
    b = draw(st.sampled_from([1, 3, 8]))
    o = draw(st.sampled_from([1, 2, 5]))
    return sizes, block, b, o


@given(layouts())
@settings(max_examples=40, deadline=None)
def test_m3_matches_brute_force(layout):
    sizes, block, b, o = layout
    pop = Population(4, o, tuple(sizes), ("relu",) * len(sizes), block=block)
    key = jax.random.PRNGKey(hash((tuple(sizes), block, b, o)) % 2**31)
    k1, k2 = jax.random.split(key)
    h = jax.random.normal(k1, (b, pop.total_hidden))
    h = h * jnp.asarray(pop.hidden_mask)        # padding units are zero
    w2 = jax.random.normal(k2, (o, pop.total_hidden))
    want = brute_force(h, w2, pop)
    got = m3(h, w2, pop)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sizes,block", [
    ((5, 17, 2, 8), 8),
    ((1, 9, 1, 4), 8),     # one-unit members, mostly padding
    ((5, 17, 2, 30), 16),
], ids=["bucketed", "one-unit", "block16"])
def test_m3_gradients_match(sizes, block):
    pop = Population(4, 3, sizes, ("relu",) * len(sizes), block=block)
    key = jax.random.PRNGKey(0)
    h = jax.random.normal(key, (6, pop.total_hidden)) \
        * jnp.asarray(pop.hidden_mask)
    w2 = jax.random.normal(jax.random.PRNGKey(1), (3, pop.total_hidden))

    # the model masks padded hidden units (h·mask), so gradients there are
    # killed downstream — compose the mask into the loss like forward() does
    mask = jnp.asarray(pop.hidden_mask)

    def loss(fn):
        return lambda hh, ww: (fn(hh * mask, ww, pop) ** 2).sum()

    want = jax.grad(loss(brute_force), argnums=(0, 1))(h, w2)
    got = jax.grad(loss(m3), argnums=(0, 1))(h, w2)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-4)


def test_m3_bf16():
    pop = Population(4, 2, (8, 16), ("relu", "relu"), block=8)
    h = jax.random.normal(jax.random.PRNGKey(0), (4, pop.total_hidden),
                          jnp.bfloat16)
    w2 = jax.random.normal(jax.random.PRNGKey(1), (2, pop.total_hidden),
                           jnp.bfloat16)
    got = np.asarray(m3(h, w2, pop), np.float32)
    want = brute_force(h.astype(jnp.float32), w2.astype(jnp.float32), pop)
    np.testing.assert_allclose(got, np.asarray(want), rtol=5e-2, atol=5e-2)
