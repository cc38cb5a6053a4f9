"""The gated short convolution of the LFM2 models on raw arrays.

``[B, C, X] = x W_in`` (hidden -> 3 x hidden, no bias), ``z = B * X``,
``c_t = sum_j w_j * z_{t-(L-1)+j}`` per channel (depthwise and causal,
kernel ``L``: zeros stand before each sequence's first token, and no
sequence of a batch reads another), ``y = (C * c) W_out``.

Plain ``jax.numpy``: the element-wise middle is a few passes over
[batch, seq, 3 x hidden] that XLA fuses with the projections' edges; a
kernel waits for a trace that prices it. The middle has its own vjp, so
that the backward pass keeps the projection's output alone and makes
``z`` and ``c`` again from it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_conv", "gated_conv", "gated_short_conv"]

F32 = jnp.float32


def _taps(z, w, lead: int):
    """sum_j w[j] * z[:, t - lead + j] over the sequence axis of z
    [b, s, c], w [L, c], zeros outside the sequence: ``lead = L - 1`` is
    the causal convolution, ``lead = 0`` with ``w`` reversed its
    transpose."""
    taps, s = w.shape[0], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (lead, taps - 1 - lead), (0, 0)))
    return sum(w[j] * zp[:, j:j + s] for j in range(taps))


def causal_conv(z, w):
    """Depthwise causal convolution along the sequence: z [b, s, c],
    w [L, c] -> c_t = sum_j w_j z_{t-(L-1)+j}, zeros before t = 0."""
    return _taps(z, w, w.shape[0] - 1)


@jax.custom_vjp
def gated_conv(bcx, w):
    """``C * causal_conv(B * X, w)`` for ``bcx = [B, C, X]`` along the
    last axis [b, s, 3c] and ``w`` [L, c]; float32 inside, ``bcx``'s
    dtype out."""
    b_, c_, x_ = jnp.split(bcx.astype(F32), 3, axis=-1)
    return (c_ * causal_conv(b_ * x_, w.astype(F32))).astype(bcx.dtype)


def _gated_conv_fwd(bcx, w):
    return gated_conv(bcx, w), (bcx, w)


def _gated_conv_bwd(res, dy):
    bcx, w = res
    taps = w.shape[0]
    b_, c_, x_ = jnp.split(bcx.astype(F32), 3, axis=-1)
    wf, dy = w.astype(F32), dy.astype(F32)
    z = b_ * x_
    d_conv = dy * c_
    # z_t feeds c_t .. c_{t+L-1}: the transposed convolution runs ahead
    dz = _taps(d_conv, wf[::-1], 0)
    s = z.shape[1]
    zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
    dw = jnp.stack([jnp.sum(d_conv * zp[:, j:j + s], axis=(0, 1))
                    for j in range(taps)])
    d_bcx = jnp.concatenate(
        [dz * x_, dy * causal_conv(z, wf), dz * b_], axis=-1)
    return d_bcx.astype(bcx.dtype), dw.astype(w.dtype)


gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def gated_short_conv(x, w_in, w_conv, w_out):
    """x [b, s, hidden]; w_in [hidden, 3 x hidden]; w_conv [L, hidden];
    w_out [hidden, hidden] -> [b, s, hidden]."""
    return gated_conv(x @ w_in, w_conv) @ w_out
