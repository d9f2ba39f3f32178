"""Rotary position embedding (Su et al. 2021, "RoFormer"), the rotate-half
pairing: channel i of a head is paired with channel i + D/2, and the pair
at position p is turned by the angle p * theta^(-2i/D):

    out[..., :D/2] = x1 cos - x2 sin
    out[..., D/2:] = x2 cos + x1 sin      (x1, x2 the two halves of x)

Angles, cos and sin are float32 whatever x's dtype (at theta 1e6 and 8,192
positions a bf16 angle would be off by whole turns); the product is formed
in float32 and returned in x's dtype.  Position 0 is the identity.
"""
from __future__ import annotations

import jax.numpy as jnp

from ._helpers import to_tensor_like
from .dispatch import apply


def rotate_half(x, theta=10000.0):
    """x [B, T, H, D] (D even), every head turned by its position, the
    index 0 .. T - 1 in the sequence."""
    T, D = x.shape[1], x.shape[-1]
    if D % 2:
        raise ValueError(f"rotary embedding needs an even head size: {D}")
    inv_freq = theta ** (-jnp.arange(D // 2, dtype=jnp.float32) * 2 / D)
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :D // 2].astype(jnp.float32)
    x2 = x[..., D // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def rotary_embedding(x, theta=10000.0, name=None):
    """Tensor entry for `rotate_half`: x [B, T, H, D] -> the same shape and
    dtype."""
    return apply("rotary_embedding", lambda v: rotate_half(v, theta),
                 to_tensor_like(x))
