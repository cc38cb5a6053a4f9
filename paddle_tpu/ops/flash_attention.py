"""Flash attention on raw arrays.

Replaces the reference's third_party/flashattn CUDA binding
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu; python API
/root/reference/python/paddle/nn/functional/flash_attention.py:146).

Two paths:
- ``flash_attention_reference``: jnp online-softmax-free reference (numerics
  oracle + CPU/test path). XLA fuses this well for moderate sequence
  lengths.
- Pallas TPU kernel (paddle_tpu/ops/pallas/flash_attention.py): blocked
  fwd/bwd with online softmax, used automatically on TPU backends for
  long sequences.

Layout is paddle's: q/k/v [batch, seq, num_heads, head_dim].

``window`` (a static integer or ``None``; causal attention only) is the
number of keys a query sees, ITS OWN COUNTED: the query at position t
sees the keys s with ``t - window < s <= t``, which is
``sliding_window`` of the public configs that count so (Mistral's,
SmallThinker's ``sliding_window_size``). ``None`` is plain causal
attention and today's program to the letter; with a window the Pallas
kernels walk only the band's tiles and are named ``flash_win_*``.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _sdpa_core(q, k, v, bias, causal, scale, dropout=0.0,
               dropout_key=None, window=None):
    """[b, s, h, d] reference attention with f32 softmax accumulation.
    dropout (with a key) is applied to the attention probabilities,
    upscale-in-train — the reference flashattn semantics. ``window``:
    the module's docstring."""
    if window is not None and not causal:
        raise ValueError("a window bounds causal attention only")
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    if kv_heads != h:  # grouped-query attention: repeat kv heads
        rep = h // kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        mask = qi >= ki
        if window is not None:
            mask = mask & (ki > qi - window)
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout,
                                    probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def flash_attention_reference(q, k, v, attn_mask=None, causal=False,
                              dropout=0.0, scale=None, window=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _sdpa_core(q, k, v, attn_mask, causal, scale, window=window)


def _pick_block(seq: int):
    for blk in (512, 256, 128):
        if seq % blk == 0:
            return blk
    return None


def pallas_attention_plan(q, k, min_seq: int = 512):
    """THE eligibility gate for the Pallas attention kernels (single
    source of truth — flash_attention, flash_attention_segmented, and
    ring attention all route through here). Returns (block_q, block_k)
    when the kernel applies, else None."""
    from .pallas import interpret
    if interpret():
        return None
    from ..utils.flags import FLAGS
    if not getattr(FLAGS, "use_pallas_kernels", True):
        return None
    if q.shape[-1] not in (64, 128, 256):
        return None
    if q.shape[1] < min_seq or k.shape[1] < min_seq:
        return None
    bq = _pick_block(q.shape[1])
    bk = _pick_block(k.shape[1])
    if bq is None or bk is None:
        return None
    return bq, bk


def flash_attention(q, k, v, attn_mask=None, causal=False, dropout=0.0,
                    scale=None, return_softmax=False, dropout_key=None,
                    window=None):
    """Differentiable flash attention on raw arrays.

    On TPU backends dispatches to the Pallas kernel (custom VJP) when
    shapes qualify (no mask, no dropout, seq divisible by a block size,
    head_dim MXU friendly); otherwise the jnp reference (XLA still fuses
    well). Both paths match numerically up to f32 accumulation order.
    Attention dropout requires a dropout_key (the dense path applies it
    to the probs); dropout > 0 without a key is an error — never a
    silent no-op. ``window``: the module's docstring.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if dropout and dropout_key is None:
        raise ValueError(
            "flash_attention: dropout > 0 needs dropout_key (the "
            "nn.functional wrappers pass one from the RNG stream when "
            "training)")
    plan = pallas_attention_plan(q, k) if (attn_mask is None
                                           and dropout == 0.0) else None
    if window is not None:      # trace-time: that a call took a window
        from ..utils import telemetry
        metrics = telemetry.default_tracer().metrics
        metrics.inc("attn.flash.window")
        metrics.set_gauge("attn.flash.window_size", window)
    if plan is not None:
        from .pallas.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal, scale, *plan, window)
    return _sdpa_core(q, k, v, attn_mask, causal, scale, dropout,
                      dropout_key, window)


# ---------------------------------------------------------------------------
# segment-masked (varlen / packed-sequence) attention
# ---------------------------------------------------------------------------

def _sdpa_segmented_core(q, k, v, q_seg, kv_seg, causal, scale):
    """Dense oracle for segment-masked attention. q/k/v [b,s,h,d]; segment
    ids [b,s]. Fully-masked query rows yield zero output."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kv_heads = k.shape[2]
    if kv_heads != h:
        rep = h // kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    mask = (q_seg[:, None, :, None] == kv_seg[:, None, None, :])  # [b,1,q,k]
    if causal:
        qi = jnp.arange(sq)[:, None] + (sk - sq)
        ki = jnp.arange(sk)[None, :]
        mask = jnp.logical_and(mask, (qi >= ki)[None, None])
    logits = jnp.where(mask, logits, _NEG_INF)
    # guarded softmax: rows with no visible keys -> zeros, not NaN
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.where(mask, jnp.exp(logits - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / jnp.maximum(l, 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)


def flash_attention_segmented(q, k, v, q_segment_ids, kv_segment_ids,
                              causal=False, scale=None):
    """Segment-masked attention, Pallas on TPU / dense reference elsewhere.
    Parity: the varlen CUDA path of
    /root/reference/python/paddle/nn/functional/flash_attention.py:302."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    plan = pallas_attention_plan(q, k)
    if plan is not None:
        from .pallas.flash_attention import (
            flash_attention_pallas_segmented)
        return flash_attention_pallas_segmented(
            q, k, v, q_segment_ids, kv_segment_ids, causal, scale, *plan)
    return _sdpa_segmented_core(q, k, v, q_segment_ids, kv_segment_ids,
                                causal, scale)


def segments_from_cu_seqlens(cu_seqlens, total: int, pad_id: int = -1):
    """cu_seqlens [n+1] (cumulative lengths, cu[0]=0) -> per-token segment
    ids [total]; tokens at/after cu[-1] get pad_id (attend nothing when
    pad ids differ between q and kv)."""
    pos = jnp.arange(total, dtype=jnp.int32)
    seg = jnp.searchsorted(cu_seqlens[1:].astype(jnp.int32), pos,
                           side="right").astype(jnp.int32)
    return jnp.where(pos < cu_seqlens[-1], seg, jnp.int32(pad_id))


def flash_attn_varlen(q, k, v, cu_seqlens_q, cu_seqlens_k,
                      max_seqlen_q=None, max_seqlen_k=None, scale=None,
                      causal=False):
    """Unpadded (packed) flash attention. q [total_q, h, d]; k/v
    [total_k, hk, d]; cu_seqlens_* [n+1] int32. Causal masking is
    per-sequence (requires the usual self-attention packing where q and k
    positions align). Returns packed out [total_q, h, d].

    Parity: flash_attn_unpadded
    (/root/reference/python/paddle/nn/functional/flash_attention.py:302,
    CUDA kernels paddle/phi/kernels/gpu/flash_attn_kernel.cu)."""
    total_q, total_k = q.shape[0], k.shape[0]
    seg_q = segments_from_cu_seqlens(cu_seqlens_q, total_q, pad_id=-1)
    seg_k = segments_from_cu_seqlens(cu_seqlens_k, total_k, pad_id=-2)
    out = flash_attention_segmented(
        q[None], k[None], v[None], seg_q[None], seg_k[None],
        causal=causal, scale=scale)
    return out[0]
