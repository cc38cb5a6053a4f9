"""Rotary position embedding on raw arrays (reference:
/root/reference/python/paddle/incubate/nn/functional/fused_rotary_position_embedding.py).
Pure jnp: XLA fuses the mul/add chain into surrounding ops; layout is
[batch, seq, heads, head_dim] (paddle convention).

Partial rotary embedding: a cache narrower than the head rotates the
first ``cos.shape[-1]`` dimensions of each head (half-split pairs inside
them) and passes the rest through. YaRN (``yarn_inv_freq``): inverse
frequencies that blend interpolation and extrapolation, with the
cache's ``scale`` as its ``attention_factor``."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope_reference(x, cos, sin):
    """x: [b, s, h, d]; cos/sin: broadcastable [1, s, 1, r], r <= d: the
    first r dimensions rotate, the other d - r pass through."""
    r = cos.shape[-1]
    if r == x.shape[-1]:
        return x * cos + _rotate_half(x) * sin
    turn, rest = x[..., :r], x[..., r:]
    return jnp.concatenate([turn * cos + _rotate_half(turn) * sin, rest],
                           axis=-1)


def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_max_position_embeddings: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0):
    """YaRN's inverse frequencies [dim / 2] for ``dim`` rotated dimensions
    (Peng et al. 2023; the ``yarn`` rope type of the public configs):
    pair i keeps its frequency ``base ** (-2i / dim)`` below the
    correction dimension of ``beta_fast`` rotations over the original
    context, takes it divided by ``factor`` above that of ``beta_slow``,
    and a linear blend of the two between them (floor and ceiling of the
    two correction dimensions)."""
    def correction_dim(rotations):
        return dim * math.log(original_max_position_embeddings
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    # 0: the pair's own frequency, 1: that over factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    own = base ** (-np.arange(0, dim, 2) / dim)
    return (own * (1.0 - ramp) + own / factor * ramp).astype(np.float32)


def build_rope_cache(seq_len: int, head_dim: int, base: float = 10000.0,
                     dtype=jnp.float32, inv_freq=None, scale: float = 1.0):
    """cos, sin [1, s, 1, head_dim] of positions 0 .. s - 1: of
    ``inv_freq`` [head_dim / 2] where it is given (``head_dim`` is then
    the rotated part's size), else of ``base``; times ``scale``."""
    if inv_freq is None:
        inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [s, d/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [s, d]
    cos, sin = jnp.cos(emb), jnp.sin(emb)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return (cos[None, :, None, :].astype(dtype),
            sin[None, :, None, :].astype(dtype))


def apply_rotary_pos_emb(q, k, cos=None, sin=None, position_ids=None,
                         base: float = 10000.0):
    """Fused-RoPE API parity: q/k [b, s, h, d]; builds cache if absent."""
    if cos is None:
        cos, sin = build_rope_cache(q.shape[1], q.shape[-1], base, q.dtype)
    if position_ids is not None:
        cos = jnp.take(cos[0], position_ids, axis=0)[:, :, None, :] if cos.shape[0] == 1 else cos
        sin = jnp.take(sin[0], position_ids, axis=0)[:, :, None, :] if sin.shape[0] == 1 else sin
    q_out = rope_reference(q, cos.astype(q.dtype), sin.astype(q.dtype))
    k_out = rope_reference(k, cos.astype(k.dtype), sin.astype(k.dtype))
    return q_out, k_out
