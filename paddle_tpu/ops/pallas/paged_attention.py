"""Paged-attention decode, Pallas TPU kernel.

Reference: block_multi_head_attention decode
(/root/reference/paddle/phi/kernels/fusion/gpu/
block_multi_head_attention_kernel.cu; python API
python/paddle/incubate/nn/functional/block_multihead_attention.py).

TPU-native design: the KV pool stays in HBM (memory_space=ANY); the
per-sequence block table and context lengths are SCALAR-PREFETCHED into
SMEM. One grid step per sequence runs a fori_loop whose trip count is the
sequence's ACTUAL page count (no work on empty pages), manually DMA-ing
each physical page — [kv_heads, block_size, head_dim], one contiguous
copy serving every kv head — into a double-buffered VMEM scratch so the
next page's DMA overlaps the current page's flash-style online-softmax
update. This is the latency story jnp.take can't express: the gather
composition materializes [batch, max_pages*block_size, ...] windows and
always pays for max_pages.

Pool layout: [num_blocks, kv_heads, block_size, head_dim].
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret

_NEG_INF = -1e30


def _decode_kernel(tables_ref, ctx_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sem_k, sem_v, *, block_size, scale,
                   pages_per_iter, max_pages):
    bi = pl.program_id(0)
    ctx = ctx_ref[bi]
    P = pages_per_iter
    n_pages = jax.lax.div(ctx + block_size - 1, block_size)
    n_iters = jax.lax.div(n_pages + P - 1, P)
    kvh, group, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0].astype(jnp.float32) * scale          # [kvh, group, d]

    def copy_in(g, slot):
        """Issue P page DMAs for iteration group g into buffer `slot`;
        each page lands in its strip of the [kvh, P*bs, d] buffer."""
        for j in range(P):
            # tail groups read a clamped table entry; masked in compute
            pj = jnp.minimum(g * P + j, max_pages - 1)
            page = tables_ref[bi, pj]
            pltpu.make_async_copy(
                k_hbm.at[page],
                k_buf.at[slot, :, pl.ds(j * block_size, block_size), :],
                sem_k.at[slot, j]).start()
            pltpu.make_async_copy(
                v_hbm.at[page],
                v_buf.at[slot, :, pl.ds(j * block_size, block_size), :],
                sem_v.at[slot, j]).start()

    def wait_group(g, slot):
        for j in range(P):
            page = tables_ref[bi, jnp.minimum(g * P + j, max_pages - 1)]
            pltpu.make_async_copy(
                k_hbm.at[page],
                k_buf.at[slot, :, pl.ds(j * block_size, block_size), :],
                sem_k.at[slot, j]).wait()
            pltpu.make_async_copy(
                v_hbm.at[page],
                v_buf.at[slot, :, pl.ds(j * block_size, block_size), :],
                sem_v.at[slot, j]).wait()

    @pl.when(n_iters > 0)
    def _prologue():
        copy_in(0, 0)

    def body(g, carry):
        acc, m_prev, l_prev = carry
        slot = jax.lax.rem(g, 2)

        @pl.when(g + 1 < n_iters)
        def _prefetch():
            copy_in(g + 1, jax.lax.rem(g + 1, 2))

        wait_group(g, slot)
        k = k_buf[slot].astype(jnp.float32)            # [kvh, P*bs, d]
        v = v_buf[slot].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [kvh, group, P*bs]
        pos = g * (P * block_size) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        s = jnp.where(pos < ctx, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        prob = jnp.where(s > _NEG_INF * 0.5,
                         jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(prob, axis=-1)
        acc = acc * corr[..., None] + jax.lax.dot_general(
            prob, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [kvh, group, d]
        return acc, m_new, l_new

    acc0 = jnp.zeros((kvh, group, d), jnp.float32)
    m0 = jnp.full((kvh, group), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((kvh, group), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, n_iters, body, (acc0, m0, l0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[..., None]).astype(o_ref.dtype)


def paged_attention_decode_pallas(q, k_cache, v_cache, block_tables,
                                  context_lens,
                                  scale: Optional[float] = None):
    """One-token decode over the paged pool.

    q [batch, num_heads, head_dim]; caches [num_blocks, kv_heads,
    block_size, head_dim]; block_tables [batch, max_pages] int32;
    context_lens [batch] int32. Returns [batch, num_heads, head_dim]."""
    b, nh, d = q.shape
    nb, kvh, bs, _ = k_cache.shape
    max_pages = block_tables.shape[1]
    group = nh // kvh
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    q4 = q.reshape(b, kvh, group, d)
    # widen each loop iteration to ~TOKENS_PER_ITER kv positions: deep
    # DMA pipeline + MXU-sized score matmuls
    import os
    tpi = int(os.environ.get("PT_PAGED_TOKENS_PER_ITER", "128"))
    P = max(1, min(max_pages, tpi // bs))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, kvh, group, d),
                         lambda bi, tbl, ctx: (bi, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, kvh, group, d),
                               lambda bi, tbl, ctx: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kvh, P * bs, d), k_cache.dtype),
            pltpu.VMEM((2, kvh, P * bs, d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, P)),
            pltpu.SemaphoreType.DMA((2, P)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_size=bs, scale=scale,
                          pages_per_iter=P, max_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
        name="paged_attn_decode",
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      q4, k_cache, v_cache)
    return out.reshape(b, nh, d)
