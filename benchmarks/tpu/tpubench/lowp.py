"""Matrix products of the plain references, at a stated precision.

``'highest'`` is the reference itself: float32 products (``Precision.HIGHEST``
on the TPU).  The two lower modes are the controls, the step below the
precision a configuration states:

* ``'high'`` — ``Precision.HIGH`` (three bfloat16 passes) on the TPU.  The
  CPU ignores ``precision``, so there it is emulated: each float32 operand
  split into a bfloat16 head and a bfloat16 tail, the three products of the
  parts other than tail x tail summed in float32, forward and backward
  (``custom_vjp``);
* ``'fp8'`` — float8 e4m3 operands (4 exponent, 3 mantissa bits), each
  scaled by its largest magnitude (per tensor), products summed in float32,
  forward and backward.

The emulated rounding is ``lax.reduce_precision``, which XLA keeps; a round
trip through a narrower dtype it may elide as excess precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MODES = ("highest", "high", "fp8")


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST, preferred_element_type=jnp.float32)


def _bf16(x):
    # rounding kept in float32 with reduce_precision, which XLA never drops
    # as excess precision (a convert to bfloat16 and back it may drop)
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _mm3(a, b):
    ah, al = _split(a)
    bh, bl = _split(b)
    return _mm(ah, bh) + (_mm(ah, bl) + _mm(al, bh))


def _q8(x):
    # e4m3: 4 exponent and 3 mantissa bits; the tensor's largest magnitude
    # scaled to 240, the top of that format without e4m3fn's extra codes
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    return jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s


def _mm8(a, b):
    return _mm(_q8(a), _q8(b))


def _lowp(product):
    @jax.custom_vjp
    def mm(a, b):
        return product(a, b)

    def fwd(a, b):
        return product(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        ga = product(g, jnp.swapaxes(b, -1, -2))
        gb = product(jnp.swapaxes(a, -1, -2), g)
        # broadcast batch dims of a 2-D operand sum back
        while gb.ndim > b.ndim:
            gb = gb.sum(0)
        while ga.ndim > a.ndim:
            ga = ga.sum(0)
        return ga, gb

    mm.defvjp(fwd, bwd)
    return mm


_MM = {"highest": _mm, "fp8": _lowp(_mm8)}
_MM3 = _lowp(_mm3)


def _mm_high(a, b):
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH,
                      preferred_element_type=jnp.float32)


def matmul(mode: str):
    """``mm(a, b)``: float32 ``a @ b`` at precision ``mode`` (``MODES``)."""
    if mode == "high":
        return _mm_high if jax.default_backend() == "tpu" else _MM3
    if mode not in _MM:
        raise ValueError(f"unknown precision {mode!r}; want one of {MODES}")
    return _MM[mode]
