"""Ragged paged attention — mixed prefill+decode rows, Pallas TPU kernel.

Reference design: "Ragged Paged Attention: A High-Performance and
Flexible LLM Inference Kernel for TPU" (PAPERS.md) — one kernel over a
FLATTENED token batch [total_rows, heads, head_dim] whose rows mix
decode tokens (one per running sequence) and prefill-chunk tokens
(consecutive prompt positions of a prefilling sequence). The grid is
sized by the actual rows, not [max_batch]: inactive batch slots simply
have no rows, so the dense path's scratch-page padding disappears at the
source.

TPU-native structure (same skeleton as the decode kernel in
paged_attention.py): the KV pool stays in HBM (memory_space=ANY);
per-row sequence ids (`row_seq`), per-row visible-context lengths
(`row_ctx`) and the per-sequence page tables are SCALAR-PREFETCHED into
SMEM. One grid step covers a block of `tq` rows: the kernel walks the
block's DISTINCT sequences (first-occurrence dedup over the prefetched
row_seq scalars — a prefill chunk contributes many rows of ONE sequence,
so its pages are DMA'd once per block, not once per row), manually
double-buffer-DMA-ing each physical page — [kv_heads, block_size,
head_dim], one contiguous copy per page — into VMEM while the previous
page's flash-style online-softmax update runs. Per-row causal masking is
pure data: pool positions >= row_ctx[row] are masked, which is both the
context-length bound AND the intra-chunk causal mask (chunk row j at
offset `off` passes row_ctx = off + j + 1).

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


def _ragged_kernel(rowseq_ref, rowctx_ref, tables_ref, q_ref, *refs,
                   block_size, scale, pages_per_iter, max_pages, tq,
                   group, quantized):
    # ref unpacking is static on `quantized` (ISSUE 13): the int8 pool
    # carries two extra HBM operands (the per-slot-per-kv-head scale
    # sidecars), two extra VMEM scale buffers and their DMA semaphores
    # — each physical page's [kvh, bs] scale row rides the SAME
    # double-buffered pipeline as its values, and dequant happens in
    # VMEM right before the score/value matmuls (quantize-the-pool,
    # dequant-at-the-DMA: the EQuARX wire idea applied to storage).
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf,
         vs_buf, sem_k, sem_v, sem_ks, sem_vs) = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sem_k, sem_v = refs
    g = pl.program_id(0)
    base = g * tq
    P = pages_per_iter
    bs = block_size
    kvh, rows, d = q_ref.shape[0], q_ref.shape[2], q_ref.shape[3]
    q = q_ref[:, 0].astype(jnp.float32) * scale        # [kvh, rows, d]

    # per-lane row maps (lane -> its row's seq id / visible ctx), built
    # once per block from tq scalar SMEM reads; lane = row * group + gi
    lane_row = jax.lax.broadcasted_iota(
        jnp.int32, (1, rows, 1), 1) // group
    seq_map = jnp.zeros((1, rows, 1), jnp.int32)
    ctx_map = jnp.zeros((1, rows, 1), jnp.int32)
    for j in range(tq):
        seq_map = jnp.where(lane_row == j, rowseq_ref[base + j], seq_map)
        ctx_map = jnp.where(lane_row == j, rowctx_ref[base + j], ctx_map)

    def _page_copies(s, it, slot, pj):
        page = tables_ref[s, jnp.minimum(it * P + pj, max_pages - 1)]
        copies = [
            pltpu.make_async_copy(
                k_hbm.at[page],
                k_buf.at[slot, :, pl.ds(pj * bs, bs), :],
                sem_k.at[slot, pj]),
            pltpu.make_async_copy(
                v_hbm.at[page],
                v_buf.at[slot, :, pl.ds(pj * bs, bs), :],
                sem_v.at[slot, pj]),
        ]
        if quantized:
            copies.append(pltpu.make_async_copy(
                ks_hbm.at[page],
                ks_buf.at[slot, :, pl.ds(pj * bs, bs)],
                sem_ks.at[slot, pj]))
            copies.append(pltpu.make_async_copy(
                vs_hbm.at[page],
                vs_buf.at[slot, :, pl.ds(pj * bs, bs)],
                sem_vs.at[slot, pj]))
        return copies

    def copy_in(s, it, slot):
        """Issue the page DMAs of sequence `s`'s iteration group `it`
        into buffer `slot` (tail groups read a clamped table entry —
        masked in compute); values + sidecar scales together."""
        for pj in range(P):
            for c in _page_copies(s, it, slot, pj):
                c.start()

    def wait_group(s, it, slot):
        for pj in range(P):
            for c in _page_copies(s, it, slot, pj):
                c.wait()

    def seq_body(j, carry):
        """Process the block's j-th row's sequence IF row j is its
        first live occurrence in the block (dedup: one page walk per
        distinct sequence per block)."""
        acc, m_prev, l_prev = carry
        s = rowseq_ref[base + j]
        ctx_j = rowctx_ref[base + j]

        def occ(i, c):
            fo, mx = c
            si = rowseq_ref[base + i]
            ci = rowctx_ref[base + i]
            fo = jnp.logical_and(
                fo, jnp.logical_or(i >= j,
                                   jnp.logical_or(si != s, ci <= 0)))
            mx = jnp.where(si == s, jnp.maximum(mx, ci), mx)
            return fo, mx

        fo, maxctx = jax.lax.fori_loop(
            0, tq, occ, (jnp.asarray(True), jnp.asarray(0, jnp.int32)))
        process = jnp.logical_and(fo, ctx_j > 0)
        n_pages = jnp.where(
            process, jax.lax.div(maxctx + bs - 1, bs), 0)
        n_iters = jax.lax.div(n_pages + P - 1, P)
        belongs = seq_map == s                         # [1, rows, 1]

        @pl.when(n_iters > 0)
        def _prologue():
            copy_in(s, 0, 0)

        def page_body(it, c):
            acc, m_prev, l_prev = c
            slot = jax.lax.rem(it, 2)

            @pl.when(it + 1 < n_iters)
            def _prefetch():
                copy_in(s, it + 1, jax.lax.rem(it + 1, 2))

            wait_group(s, it, slot)
            k = k_buf[slot].astype(jnp.float32)        # [kvh, P*bs, d]
            v = v_buf[slot].astype(jnp.float32)
            if quantized:
                # dequant in VMEM, per element, exactly the oracle's
                # gather-time math (value * its slot's scale) so
                # kernel-vs-oracle parity holds bit-tight on the int8
                # layout; the scale buffers are [kvh, P*bs]
                k = k * ks_buf[slot][..., None]
                v = v * vs_buf[slot][..., None]
            sc = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)    # [kvh, rows, P*bs]
            pos = it * (P * bs) + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 2)
            ok = jnp.logical_and(belongs, pos < ctx_map)
            sc = jnp.where(ok, sc, _NEG_INF)
            m_cur = jnp.max(sc, axis=-1)
            m_new = jnp.maximum(m_prev, m_cur)
            prob = jnp.where(sc > _NEG_INF * 0.5,
                             jnp.exp(sc - m_new[..., None]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_new = l_prev * corr + jnp.sum(prob, axis=-1)
            acc = acc * corr[..., None] + jax.lax.dot_general(
                prob, v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)    # [kvh, rows, d]
            return acc, m_new, l_new

        return jax.lax.fori_loop(0, n_iters, page_body,
                                 (acc, m_prev, l_prev))

    acc0 = jnp.zeros((kvh, rows, d), jnp.float32)
    m0 = jnp.full((kvh, rows), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((kvh, rows), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, tq, seq_body, (acc0, m0, l0))
    # rows no sequence claimed (grid padding, row_ctx <= 0) have l == 0
    # and come out exactly zero
    o_ref[:, 0] = (acc / jnp.maximum(l, 1e-30)[..., None]) \
        .astype(o_ref.dtype)


def ragged_paged_attention_pallas(q, k_cache, v_cache, block_tables,
                                  row_seq, row_ctx,
                                  scale: Optional[float] = None,
                                  rows_per_block: int = 8):
    """Ragged mixed prefill+decode attention over the paged pool.

    q [total_rows, num_heads, head_dim]; caches [num_blocks, kv_heads,
    block_size, head_dim] — or (int8 values, f32 scales [num_blocks,
    kv_heads, block_size]) tuples for the quantized pool (ISSUE 13),
    whose sidecar scales ride each page's DMA and dequantize in VMEM;
    block_tables [num_seqs, max_pages] int32;
    row_seq/row_ctx [total_rows] int32 (see
    ops.paged_attention.ragged_paged_attention_reference).
    Returns [total_rows, num_heads, head_dim]."""
    quantized = isinstance(k_cache, tuple)
    if quantized:
        k_cache, k_scales = k_cache
        v_cache, v_scales = v_cache
    r, nh, d = q.shape
    nb, kvh, bs, _ = k_cache.shape
    max_pages = block_tables.shape[1]
    if nh % kvh:
        # would otherwise surface as an opaque reshape error below;
        # matters doubly under TP sharding, where SpecLayout shards the
        # pool over the kv-head dim and each shard's nh/kvh must still
        # group evenly
        raise ValueError(
            f"num_heads ({nh}) must be a multiple of kv_heads ({kvh}) "
            f"for the GQA head grouping")
    group = nh // kvh
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    tq = max(1, int(rows_per_block))
    g = -(-r // tq)
    r_pad = g * tq
    qp = jnp.pad(q, ((0, r_pad - r), (0, 0), (0, 0)))
    rs = jnp.pad(row_seq.astype(jnp.int32), (0, r_pad - r))
    rc = jnp.pad(row_ctx.astype(jnp.int32), (0, r_pad - r),
                 constant_values=0)
    # [kvh, grid, tq*group, d]: kv-head-major so the kernel's score
    # matmul is the decode kernel's 3-D batched dot, no in-kernel
    # transposes
    q4 = qp.reshape(r_pad, kvh, group, d).transpose(1, 0, 2, 3) \
        .reshape(kvh, g, tq * group, d)
    # widen each DMA iteration to ~TOKENS_PER_ITER kv positions (deep
    # pipeline + MXU-sized score matmuls), same knob as the decode kernel
    import os
    tpi = int(os.environ.get("PT_PAGED_TOKENS_PER_ITER", "128"))
    P = max(1, min(max_pages, tpi // bs))

    in_specs = [
        pl.BlockSpec((kvh, 1, tq * group, d),
                     lambda gi, rs_, rc_, tb_: (0, gi, 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),   # K pool stays in HBM
        pl.BlockSpec(memory_space=pl.ANY),   # V pool stays in HBM
    ]
    scratch_shapes = [
        pltpu.VMEM((2, kvh, P * bs, d), k_cache.dtype),
        pltpu.VMEM((2, kvh, P * bs, d), v_cache.dtype),
    ]
    sems = [pltpu.SemaphoreType.DMA((2, P)),
            pltpu.SemaphoreType.DMA((2, P))]
    operands = [k_cache, v_cache]
    if quantized:
        # scale sidecars: HBM-resident like the pools, double-buffered
        # [kvh, P*bs] f32 VMEM slices, one DMA semaphore pair more
        in_specs += [pl.BlockSpec(memory_space=pl.ANY),
                     pl.BlockSpec(memory_space=pl.ANY)]
        scratch_shapes += [pltpu.VMEM((2, kvh, P * bs), jnp.float32),
                           pltpu.VMEM((2, kvh, P * bs), jnp.float32)]
        sems += [pltpu.SemaphoreType.DMA((2, P)),
                 pltpu.SemaphoreType.DMA((2, P))]
        operands += [k_scales, v_scales]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(g,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((kvh, 1, tq * group, d),
                               lambda gi, rs_, rc_, tb_: (0, gi, 0, 0)),
        scratch_shapes=scratch_shapes + sems,
    )
    out = pl.pallas_call(
        functools.partial(_ragged_kernel, block_size=bs, scale=scale,
                          pages_per_iter=P, max_pages=max_pages, tq=tq,
                          group=group, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((kvh, g, tq * group, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret(),
        name="ragged_paged_attn",
    )(rs, rc, block_tables.astype(jnp.int32), q4, *operands)
    out = out.reshape(kvh, r_pad, group, d).transpose(1, 0, 2, 3) \
        .reshape(r_pad, nh, d)
    return out[:r]
