"""Kernel-side forms of the ten paper activations and their derivatives.

``core/activations.py`` holds the exact ``jax.numpy`` definitions (the
reference every test compares against).  Three of them lower through
primitives Mosaic has no TPU lowering for — ``elu`` and ``selu`` through
``expm1``, exact ``gelu`` through ``erfc`` — and a kernel epilogue that
dispatches through ``lax.switch`` traces EVERY branch, so one unlowerable
activation refuses the kernel for every population.  The forms here use
only ``exp``, ``tanh``, ``log1p``, ``min``/``max``/``where`` and f32
arithmetic:

  elu / selu   ``exp(min(x, 0)) - 1`` in place of ``expm1``
  gelu         ``0.5·x·(1 + erf(x/√2))`` with a rational f32 ``erf``
               (the clamped odd rational form XLA itself emits for f32)

The derivatives of those three are written out (the exact derivative
evaluated with the same building blocks); the other seven derive theirs
by ``vjp`` at ones.  tests/test_chip_smoke.py bounds value and
derivative against the exact reference over ±20.

``VAL_BRANCHES`` / ``VAL_DERIV_BRANCHES`` are the ``lax.switch`` tables
of every fused epilogue (forward, forward+derivative), indexed by the
canonical ``ACTIVATION_ORDER`` id.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.activations import ACTIVATION_ORDER, ACTIVATIONS

_SELU_ALPHA = 1.6732632423543772848170429916717
_SELU_SCALE = 1.0507009873554804934193349852946
_INV_SQRT2 = 1.0 / math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)

# erf(x) ≈ x·P(x²)/Q(x²) on |x| ≤ erfinv(1 − 2⁻²³), ±1 outside
_ERF_CLAMP = 3.7439211627767994
_ERF_P = (0.00022905065861350646, 0.0034082910107109506,
          0.050955695062380861, 0.18520832239976145, 1.128379143519084)
_ERF_Q = (-1.1791602954361697e-7, 0.000023547966471313185,
          0.0010179625278914885, 0.014070470171167667,
          0.11098505178285362, 0.49746925110067538, 1.0)


def _poly(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def erf(x):
    """f32 ``erf`` from exp-free rational arithmetic (Mosaic lowers no
    ``erf``/``erfc``)."""
    x = jnp.clip(x, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x
    return x * _poly(x2, _ERF_P) / _poly(x2, _ERF_Q)


def _expm1_nonpos(x):
    return jnp.exp(jnp.minimum(x, 0.0)) - 1.0


def elu(x):
    return jnp.where(x > 0, x, _expm1_nonpos(x))


def elu_deriv(x):
    return jnp.where(x > 0, jnp.ones_like(x), jnp.exp(jnp.minimum(x, 0.0)))


def selu(x):
    return _SELU_SCALE * jnp.where(x > 0, x, _SELU_ALPHA * _expm1_nonpos(x))


def selu_deriv(x):
    return _SELU_SCALE * jnp.where(
        x > 0, jnp.ones_like(x), _SELU_ALPHA * jnp.exp(jnp.minimum(x, 0.0)))


def gelu(x):
    return 0.5 * x * (1.0 + erf(x * _INV_SQRT2))


def gelu_deriv(x):
    # Φ(x) + x·φ(x)
    return (0.5 * (1.0 + erf(x * _INV_SQRT2))
            + x * _INV_SQRT_2PI * jnp.exp(-0.5 * x * x))


def _vjp_deriv(fn):
    def d(x):
        return jax.vjp(fn, x)[1](jnp.ones_like(x))[0]
    return d


_KERNEL_FORMS = {
    "elu": (elu, elu_deriv),
    "selu": (selu, selu_deriv),
    "gelu": (gelu, gelu_deriv),
}

# (value, derivative) per activation id, in ACTIVATION_ORDER
KERNEL_ACTIVATIONS = tuple(
    _KERNEL_FORMS.get(name, (ACTIVATIONS[name],
                             _vjp_deriv(ACTIVATIONS[name])))
    for name in ACTIVATION_ORDER)

VAL_BRANCHES = tuple(fn for fn, _ in KERNEL_ACTIVATIONS)
VAL_DERIV_BRANCHES = tuple(
    (lambda fn, d: (lambda x: (fn(x), d(x))))(fn, d)
    for fn, d in KERNEL_ACTIVATIONS)
DERIV_BRANCHES = tuple(d for _, d in KERNEL_ACTIVATIONS)
