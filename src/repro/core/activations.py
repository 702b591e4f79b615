"""The paper's ten activation functions and the ways of applying a
*different* activation to different column slices of a fused hidden tensor.

Strategies (cross-validated against each other in tests):
  * ``apply_activations_sliced``  — static contiguous slices, one pass per run
    (efficient when the population is sorted by activation; what XLA fuses
    best) — the ``bd_impl="einsum"`` path.
  * ``apply_activations_masked``  — branchless select over all 10 functions
    (the paper's masking strawman; used as oracle).
  * the fused kernels' epilogues (kernels/epilogue.py) — a tile-wise
    ``lax.switch`` on a scalar-prefetched activation id, applied while the
    projection's accumulator is in VMEM — the ``bd_impl="fused"`` path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------- #
# the 10 paper activations                                               #
# ---------------------------------------------------------------------- #

def _identity(x):
    return x

def _sigmoid(x):
    return jax.nn.sigmoid(x)

def _tanh(x):
    return jnp.tanh(x)

def _relu(x):
    return jax.nn.relu(x)

def _elu(x):
    return jax.nn.elu(x)

def _selu(x):
    return jax.nn.selu(x)

def _gelu(x):
    return jax.nn.gelu(x, approximate=False)

def _leaky_relu(x):
    return jax.nn.leaky_relu(x)  # slope 0.01, torch default

def _hardshrink(x, lambd: float = 0.5):
    return jnp.where((x > lambd) | (x < -lambd), x, jnp.zeros_like(x))

def _mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


ACTIVATIONS = {
    "identity": _identity,
    "sigmoid": _sigmoid,
    "tanh": _tanh,
    "relu": _relu,
    "elu": _elu,
    "selu": _selu,
    "gelu": _gelu,
    "leaky_relu": _leaky_relu,
    "hardshrink": _hardshrink,
    "mish": _mish,
}
ACTIVATION_NAMES = frozenset(ACTIVATIONS)
# canonical id order — shared with Population.act_ids and the Pallas kernel
ACTIVATION_ORDER = tuple(sorted(ACTIVATIONS))
ACTIVATION_FNS = tuple(ACTIVATIONS[n] for n in ACTIVATION_ORDER)
PAPER_TEN = ("identity", "sigmoid", "tanh", "relu", "elu", "selu", "gelu",
             "leaky_relu", "hardshrink", "mish")


# ---------------------------------------------------------------------- #
# segmented application                                                  #
# ---------------------------------------------------------------------- #

def apply_activations_sliced(h: jax.Array, runs) -> jax.Array:
    """Apply per-run activations to contiguous column slices.

    ``runs`` is ``Population.act_runs``: static (name, start, stop) triples.
    One elementwise pass per run; with a sorted population that's at most 10
    passes, each over a disjoint slice (total work = one pass over ``h``).
    """
    pieces = [ACTIVATIONS[name](h[..., start:stop]) for name, start, stop in runs]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=-1)


def apply_activations_masked(h: jax.Array, act_ids: np.ndarray) -> jax.Array:
    """Branchless: evaluate all 10 activations everywhere, select by id.
    10x elementwise flops (cheap next to the matmuls) — serves as the oracle
    and as the fallback when the population is not sorted."""
    ids = jnp.asarray(act_ids)
    out = jnp.zeros_like(h)
    for i, fn in enumerate(ACTIVATION_FNS):
        out = jnp.where(ids == i, fn(h), out)
    return out
