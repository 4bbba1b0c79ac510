"""Float32 transcendentals for Pallas TPU kernels, accurate to a couple
of float32 ulps, built from +, -, *, / and bit casts only.

The TPU's own ``log1p`` (Mosaic and XLA alike) is off by ~1e-5
relative — 7.0e-6 absolute on the fast_features length feature on a
v5e chip — which breaks the kernels' 1e-6 parity with their float64
host oracles. These replace it where a kernel's parity depends on it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN2 = 0.6931471805599453
SQRT2 = 1.4142135623730951


def log(x):
    """Natural log of positive normal float32 ``x``.

    x = m * 2^e with m in [sqrt(1/2), sqrt(2)); log m = 2 atanh(s),
    s = (m - 1) / (m + 1), |s| <= 0.172, by its odd series to s^9
    (the next term is below 7e-10)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    e = (bits >> 23) - 127
    m = jax.lax.bitcast_convert_type((bits & 0x7FFFFF) | 0x3F800000,
                                     jnp.float32)         # [1, 2)
    big = m > SQRT2
    m = jnp.where(big, m * 0.5, m)
    e = jnp.where(big, e + 1, e)
    s = (m - 1.0) / (m + 1.0)
    s2 = s * s
    series = s * (2.0 + s2 * (2.0 / 3.0 + s2 * (2.0 / 5.0 + s2 * (
        2.0 / 7.0 + s2 * (2.0 / 9.0)))))
    return e.astype(jnp.float32) * LN2 + series


LN2_HI = 0.693145751953125            # LN2 split: k * LN2_HI is exact
LN2_LO = 1.428606765330187e-06


def exp(x):
    """e^x for float32 ``x`` <= 88 (flushes to 0 below -87).

    x = k ln2 + r with |r| <= ln2 / 2 (Cody-Waite), e^r by its Taylor
    series to r^7, 2^k by building the float32 exponent bits."""
    x = jnp.maximum(x, -87.0)
    k = jnp.floor(x * (1.0 / LN2) + 0.5)
    r = (x - k * LN2_HI) - k * LN2_LO
    p = 1.0 + r * (1.0 + r * (1.0 / 2 + r * (1.0 / 6 + r * (1.0 / 24 + r * (
        1.0 / 120 + r * (1.0 / 720 + r * (1.0 / 5040)))))))
    scale = jax.lax.bitcast_convert_type(
        (k.astype(jnp.int32) + 127) << 23, jnp.float32)
    return p * scale
