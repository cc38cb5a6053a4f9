"""Flash attention, Pallas TPU implementation (fwd + bwd), with optional
segment-ids (varlen/packed-sequence) masking.

Replaces the reference's third_party/flashattn CUDA kernels
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu; varlen API
/root/reference/python/paddle/nn/functional/flash_attention.py:302).
Blocked online-softmax over KV tiles; LSE saved for the backward; causal
masking with early loop exit.

GQA is handled WITHOUT expanding K/V in HBM: forward and dq kernels read
the shared kv-head block via index maps (hi // group), and the dk/dv
kernel accumulates the query-head group in-place by revisiting the same
output block across the innermost grid dimension — no jnp.repeat, no
group-expanded HBM traffic.

Segment ids (int32, [batch, seq]) restrict attention to tokens of equal
id — the packed-sequence ("varlen"/"unpadded") training path. Negative or
mismatched ids are fully masked; fully-masked query rows produce zero
output (guarded online softmax, not NaN).

A window (``window`` keys, static; causal attention only) restricts a
query at position t to the keys s with ``t - window < s <= t``: its own
key and the ``window - 1`` before it. The three kernels then bound their
inner loops to the band's blocks (the key loops from below, the query
loop of dk/dv from above) and mask the band's lower edge beside the
causal compare, so tiles outside the band are neither fetched nor
computed; such calls are named ``flash_win_*``. ``window=None`` is the
program without any of it.

Layout contract (paddle convention at the API): q/k/v [batch, seq, heads,
head_dim]; kernels internally run [batch, heads, seq, head_dim]. On the
v5e head_dim 64 compiles and reads 21.5% of peak, 128 reads 38.9% (PR 34).

VMEM budget: the forward kernel holds K and V whole per (batch,
kv-head), and the pipeline buffers each twice: at seq 16k, d=128, bf16
that is 16 MB and past the 16 MB a kernel gets by default, so the call
asks for what it holds (``_vmem_room``; shapes that fit ask for nothing).
Longer sequences belong to ring attention
(paddle_tpu.distributed.ring_attention) which shards seq over the mesh.
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import interpret as _interpret


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying `like`'s varying-manual-axes tag: when
    a kernel runs inside a check_vma shard_map (e.g. ring attention
    manual over 'sep' with dp/mp auto), pallas_call demands the output
    vma be stated explicitly — propagate it from an input operand."""
    vma = getattr(getattr(like, "aval", None), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _band_first_block(q_first, k_first, block_k, num_kv, window):
    """First block of ``block_k`` keys (the blocks start at global
    position ``k_first``) that holds a key the query at global position
    ``q_first`` sees under ``window``: a key above ``q_first - window``."""
    lo = jnp.maximum(q_first - window + 1 - k_first, 0)
    return jnp.clip(jax.lax.div(lo, block_k), 0, num_kv)


def _band_end_block(k_last, q_first, block_q, num_q, window):
    """One past the last block of ``block_q`` queries (starting at global
    position ``q_first``) that holds a query which sees the key at global
    position ``k_last`` under ``window``: a query below ``k_last +
    window``."""
    hi = jnp.maximum(k_last + window - 1 - q_first + block_q, 0)
    return jnp.clip(jax.lax.div(hi, block_q), 0, num_q)


def _vmem_room(resident_bytes):
    """``pallas_call`` options for a call that holds ``resident_bytes`` of
    whole-sequence operands, which the pipeline buffers twice: nothing
    while they fit the VMEM a kernel gets by default, else a limit that
    holds them."""
    need = 2 * resident_bytes + (8 << 20)
    if need <= 16 << 20:
        return {}
    return {"compiler_params": pltpu.CompilerParams(vmem_limit_bytes=need)}


def _names(window):
    """The three calls' names in the compiled program and the trace."""
    stem = "flash" if window is None else "flash_win"
    return f"{stem}_fwd", f"{stem}_bwd_dq", f"{stem}_bwd_dkv"


def _fwd_kernel(*refs, scale, causal, block_k, seq_q, seq_k, segmented,
                window=None):
    if segmented:
        q_ref, k_ref, v_ref, qseg_ref, kseg_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    # block shapes: q [1, 1, bq, d]; k/v [1, 1, seq_k, d]
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, d]
    bq = q.shape[0]
    qi = pl.program_id(2)
    q_offset = qi * bq
    if segmented:
        qseg = qseg_ref[0]                                # [bq]

    num_kv = pl.cdiv(seq_k, block_k)
    off = seq_k - seq_q   # causal aligns queries to the END of the keys
    if causal:
        # only blocks whose start <= last query row's global position
        num_kv_run = jnp.maximum(
            jax.lax.div(q_offset + bq - 1 + off, block_k) + 1, 0)
    else:
        num_kv_run = num_kv
    first_kv = 0
    if window is not None:
        first_kv = _band_first_block(q_offset + off, 0, block_k, num_kv,
                                     window)

    def body(kj, carry):
        acc, m_prev, l_prev = carry
        k_blk = k_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [bq, bk]
        if causal:
            rows = q_offset + off + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
            if window is not None:
                s = jnp.where(cols > rows - window, s, _NEG_INF)
        if segmented:
            kseg = kseg_ref[0, pl.ds(kj * block_k, block_k)]  # [bk]
            s = jnp.where(qseg[:, None] == kseg[None, :], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)                          # [bq]
        m_new = jnp.maximum(m_prev, m_cur)
        # guard: fully-masked rows keep p == 0 (else exp(-inf - -inf) = 1)
        p = jnp.where(s > _NEG_INF * 0.5,
                      jnp.exp(s - m_new[:, None]), 0.0)      # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                       # [bq]
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    d = q.shape[-1]
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(first_kv, num_kv_run, body,
                                  (acc0, m0, l0))

    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, :, 0] = (m + jnp.log(l_safe)).astype(jnp.float32)


def _flash_fwd(q, k, v, q_seg, kv_seg, causal, scale, block_q, block_k,
               window=None):
    """q [b,h,sq,d]; k/v [b,hk,sk,d]; segs [b,s] or None
    → out [b,h,sq,d], lse [b,h,sq]."""
    if window is not None and not causal:
        raise ValueError("a window bounds causal attention only")
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = h // hk
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    grid = (b, h, pl.cdiv(sq, bq))
    segmented = q_seg is not None

    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_k=bk, seq_q=sq, seq_k=sk,
                               segmented=segmented, window=window)
    in_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
    ]
    args = [q, k, v]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, bq), lambda bi, hi, qi: (bi, qi)),
            pl.BlockSpec((1, sk), lambda bi, hi, qi: (bi, 0)),
        ]
        args += [q_seg, kv_seg]
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            _sds((b, h, sq, d), q.dtype, q),
            _sds((b, h, sq, 1), jnp.float32, q),
        ],
        interpret=_interpret(),
        name=_names(window)[0],
        **_vmem_room(2 * sk * d * k.dtype.itemsize),
    )(*args)
    return out, lse[..., 0]


def _bwd_dq_kernel(*refs, scale, causal, block_k, seq_q, seq_k,
                   segmented, q_base, k_base, window=None):
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
    q = q_ref[0, 0].astype(jnp.float32)                     # [bq, d]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, :, 0]                               # [bq]
    delta = delta_ref[0, 0, :, 0]                           # [bq]
    bq = q.shape[0]
    qi = pl.program_id(2)
    q_offset = qi * bq
    if segmented:
        qseg = qseg_ref[0]

    # q_base/k_base: GLOBAL sequence positions of this call's first
    # query/key row — the wrapper may be feeding a [q-chunk, k-chunk]
    # slice of a longer sequence (VMEM-bounded long-seq backward)
    num_kv = pl.cdiv(seq_k, block_k)
    if causal:
        num_kv_run = jnp.clip(
            jax.lax.div(q_base + q_offset + bq - 1 - k_base, block_k)
            + 1, 0, num_kv)
    else:
        num_kv_run = num_kv
    first_kv = 0
    if window is not None:
        first_kv = _band_first_block(q_base + q_offset, k_base, block_k,
                                     num_kv, window)

    def body(kj, dq):
        k_blk = k_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window is not None:
            rows = q_base + q_offset + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_base + kj * block_k + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if causal:
                s = jnp.where(rows >= cols, s, _NEG_INF)
            if window is not None:
                s = jnp.where(cols > rows - window, s, _NEG_INF)
        if segmented:
            kseg = kseg_ref[0, pl.ds(kj * block_k, block_k)]
            s = jnp.where(qseg[:, None] == kseg[None, :], s, _NEG_INF)
        p = jnp.where(s > _NEG_INF * 0.5,
                      jnp.exp(s - lse[:, None]), 0.0)        # [bq, bk]
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale               # [bq, bk]
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq0 = jnp.zeros_like(q)
    dq = jax.lax.fori_loop(first_kv, num_kv_run, body, dq0)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, block_q, seq_q, seq_k, group,
                    segmented, q_base, k_base, window=None):
    """Grid (b, hk, n_kblocks, group): the innermost `group` dimension
    revisits the same dk/dv output block, accumulating the kv-head's query
    group in VMEM (GQA without expanding K/V or group-partial HBM writes)."""
    if segmented:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qseg_ref,
         kseg_ref, dk_ref, dv_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref) = refs
    k_blk = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    bk = k_blk.shape[0]
    kj = pl.program_id(2)
    gi = pl.program_id(3)
    k_offset = kj * bk
    if segmented:
        kseg = kseg_ref[0, pl.ds(k_offset, bk)]

    num_q = pl.cdiv(seq_q, block_q)
    if causal:
        # first q block whose END global position can see this k block
        first_q = jax.lax.div(
            jnp.maximum(k_base + k_offset - q_base, 0), block_q)
    else:
        first_q = 0
    end_q = num_q
    if window is not None:
        end_q = _band_end_block(k_base + k_offset + bk - 1, q_base, block_q,
                                num_q, window)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(qi * block_q, block_q), 0]
        delta = delta_ref[0, 0, pl.ds(qi * block_q, block_q), 0]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal or window is not None:
            rows = q_base + qi * block_q + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = k_base + k_offset + \
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            if causal:
                s = jnp.where(rows >= cols, s, _NEG_INF)
            if window is not None:
                s = jnp.where(cols > rows - window, s, _NEG_INF)
        if segmented:
            qseg = qseg_ref[0, pl.ds(qi * block_q, block_q)]
            s = jnp.where(qseg[:, None] == kseg[None, :], s, _NEG_INF)
        p = jnp.where(s > _NEG_INF * 0.5,
                      jnp.exp(s - lse[:, None]), 0.0)        # [bq, bk]
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    d = k_blk.shape[-1]
    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(first_q, end_q, body, (dk0, dv0))

    @pl.when(gi == 0)
    def _init():
        dk_ref[0, 0] = dk
        dv_ref[0, 0] = dv

    @pl.when(gi > 0)
    def _accum():
        dk_ref[0, 0] += dk
        dv_ref[0, 0] += dv


@functools.partial(
    jax.jit, inline=True,
    static_argnames=("causal", "scale", "bq", "bk", "group", "q_base",
                     "k_base", "dq_dtype", "window", "names", "interpret"))
def _bwd_pair_call(q, k, v, do, lse4, delta, q_seg, kv_seg, *, causal,
                   scale, bq, bk, group, q_base, k_base, dq_dtype,
                   window, names, interpret):
    """dq + dk/dv pallas calls for one (q-slice, k-slice) pair whose
    first rows sit at GLOBAL positions q_base/k_base. ``window`` is None
    for a pair the band's lower edge does not cut; ``names`` are the
    whole attention's.

    An inlined ``jit``: pairs of the same shapes and static arguments
    are traced once (at 16,384 tokens a layer has 36 pairs, 28 of them
    alike) and each call still lands in the caller's program as its own
    two kernels, under the caller's scope."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    segmented = q_seg is not None

    dq_specs = [
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
        pl.BlockSpec((1, 1, sk, d),
                     lambda bi, hi, qi, _g=group: (bi, hi // _g, 0, 0)),
        pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
        pl.BlockSpec((1, 1, bq, 1), lambda bi, hi, qi: (bi, hi, qi, 0)),
    ]
    dq_args = [q, k, v, do, lse4, delta]
    if segmented:
        dq_specs += [
            pl.BlockSpec((1, bq), lambda bi, hi, qi: (bi, qi)),
            pl.BlockSpec((1, sk), lambda bi, hi, qi: (bi, 0)),
        ]
        dq_args += [q_seg, kv_seg]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_k=bk, seq_q=sq, seq_k=sk,
                          segmented=segmented, q_base=q_base,
                          k_base=k_base, window=window),
        grid=(b, h, pl.cdiv(sq, bq)),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi: (bi, hi, qi, 0)),
        out_shape=_sds((b, h, sq, d), dq_dtype, q),
        interpret=interpret,
        name=names[1],
    )(*dq_args)

    # dk/dv: grid (b, hk, kblocks, group); q-head = hk_index*group + g
    def qmap(bi, hki, kj, g, _g=group):
        return (bi, hki * _g + g, 0, 0)

    dkv_specs = [
        pl.BlockSpec((1, 1, sq, d), qmap),
        pl.BlockSpec((1, 1, bk, d), lambda bi, hki, kj, g: (bi, hki, kj, 0)),
        pl.BlockSpec((1, 1, bk, d), lambda bi, hki, kj, g: (bi, hki, kj, 0)),
        pl.BlockSpec((1, 1, sq, d), qmap),
        pl.BlockSpec((1, 1, sq, 1), qmap),
        pl.BlockSpec((1, 1, sq, 1), qmap),
    ]
    dkv_args = [q, k, v, do, lse4, delta]
    if segmented:
        dkv_specs += [
            pl.BlockSpec((1, sq), lambda bi, hki, kj, g: (bi, 0)),
            pl.BlockSpec((1, sk), lambda bi, hki, kj, g: (bi, 0)),
        ]
        dkv_args += [q_seg, kv_seg]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, seq_q=sq, seq_k=sk, group=group,
                          segmented=segmented, q_base=q_base,
                          k_base=k_base, window=window),
        grid=(b, hk, pl.cdiv(sk, bk), group),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hki, kj, g: (bi, hki, kj, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, hki, kj, g: (bi, hki, kj, 0)),
        ],
        out_shape=[
            _sds((b, hk, sk, d), jnp.float32, q),
            _sds((b, hk, sk, d), jnp.float32, q),
        ],
        interpret=interpret,
        name=names[2],
    )(*dkv_args)
    return dq, dk, dv


# backward VMEM story: each dq call holds its k-slice (and each dkv call
# its q-slice) whole in VMEM, so slices past ~2k at d=128 blow the
# ~16MB scoped-vmem budget (measured: a 4096 slice needs 16.6MB).
# Above this length the wrapper tiles the backward into
# [q-chunk, k-chunk] pair calls (global offsets keep the causal mask
# exact; fully-invisible pairs are skipped outright).
BWD_SEQ_CHUNK = 2048


def _bwd_pairs(sq, sk, causal, window):
    """The [q-chunk, k-chunk] pairs the backward pass calls its kernels
    on, in order: (q0, qe, k0, ke, whether the causal diagonal cuts the
    pair, the window if the band's lower edge does, else None). A pair
    that holds no visible (query, key) is left out."""
    cs = BWD_SEQ_CHUNK
    base = sk - sq     # causal aligns queries to the END of the keys
    for q0 in range(0, sq, cs):
        qe = min(q0 + cs, sq)
        for k0 in range(0, sk, cs):
            ke = min(k0 + cs, sk)
            if causal and k0 > base + qe - 1:
                continue                       # fully invisible pair
            if window is not None and ke - 1 <= base + q0 - window:
                continue                       # wholly below the band
            yield (q0, qe, k0, ke, causal and (ke - 1 > base + q0),
                   window if window is not None
                   and k0 <= base + qe - 1 - window else None)


def _flash_bwd(q, k, v, out, lse, do, q_seg, kv_seg, causal, scale,
               block_q, block_k, window=None):
    """q/do [b,h,sq,d]; k/v [b,hk,sk,d] (NOT expanded). Returns dq [b,h,..]
    and group-summed dk/dv [b,hk,sk,d] (float32)."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = h // hk
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[..., None]                      # [b,h,sq,1]
    lse4 = lse[..., None]                                    # [b,h,sq,1]

    cs = BWD_SEQ_CHUNK
    base = sk - sq     # causal aligns queries to the END of the keys
    static = dict(scale=scale, group=group, names=_names(window),
                  interpret=_interpret())
    if sq <= cs and sk <= cs:
        return _bwd_pair_call(q, k, v, do, lse4, delta, q_seg, kv_seg,
                              causal=causal, bq=bq, bk=bk, q_base=base,
                              k_base=0, dq_dtype=q.dtype, window=window,
                              **static)

    dq = jnp.zeros((b, h, sq, d), jnp.float32)
    dk = jnp.zeros((b, hk, sk, d), jnp.float32)
    dv = jnp.zeros((b, hk, sk, d), jnp.float32)
    for q0, qe, k0, ke, pair_causal, pair_window in _bwd_pairs(
            sq, sk, causal, window):
        # a pair that neither mask cuts never reads its positions: given
        # as 0, all such pairs are one trace
        cut = pair_causal or pair_window is not None
        dq_p, dk_p, dv_p = _bwd_pair_call(
            q[:, :, q0:qe], k[:, :, k0:ke], v[:, :, k0:ke],
            do[:, :, q0:qe], lse4[:, :, q0:qe],
            delta[:, :, q0:qe],
            None if q_seg is None else q_seg[:, q0:qe],
            None if kv_seg is None else kv_seg[:, k0:ke],
            causal=pair_causal, bq=min(bq, qe - q0), bk=min(bk, ke - k0),
            q_base=base + q0 if cut else 0, k_base=k0 if cut else 0,
            dq_dtype=jnp.float32, window=pair_window, **static)
        dq = dq.at[:, :, q0:qe].add(dq_p)
        dk = dk.at[:, :, k0:ke].add(dk_p)
        dv = dv.at[:, :, k0:ke].add(dv_p)
    return dq.astype(q.dtype), dk, dv


# ---------------------------------------------------------------------------
# public custom-vjp entry points
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_pallas(q, k, v, causal=False, scale=None,
                           block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                           window=None):
    """q/k/v: [batch, seq, heads, head_dim] (kv heads may be fewer: GQA);
    ``window``: a static number of keys a causal query sees, its own
    counted, or None."""
    out, _ = _fa_fwd(q, k, v, causal, scale, block_q, block_k, window)
    return out


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, window=None):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)   # [b,h,s,d]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t, lse = _flash_fwd(qt, kt, vt, None, None, causal, scale,
                            block_q, block_k, window)
    out, lse = _kept(jnp.swapaxes(out_t, 1, 2), lse)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, window, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t = jnp.swapaxes(out, 1, 2)
    do_t = jnp.swapaxes(g, 1, 2)
    dq_t, dk_t, dv_t = _flash_bwd(qt, kt, vt, out_t, lse, do_t, None, None,
                                  causal, scale, block_q, block_k, window)
    dq = jnp.swapaxes(dq_t, 1, 2).astype(q.dtype)
    dk = jnp.swapaxes(dk_t, 1, 2).astype(k.dtype)
    dv = jnp.swapaxes(dv_t, 1, 2).astype(v.dtype)
    return dq, dk, dv


flash_attention_pallas.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def flash_attention_pallas_segmented(q, k, v, q_segment_ids, kv_segment_ids,
                                     causal=False, scale=None,
                                     block_q=DEFAULT_BLOCK_Q,
                                     block_k=DEFAULT_BLOCK_K):
    """Segment-masked (varlen/packed) flash attention.

    q/k/v: [batch, seq, heads, head_dim]; segment ids [batch, seq] int32.
    Tokens attend only to equal segment ids (intersected with causal);
    rows with no visible keys output zeros."""
    out, _ = _fas_fwd(q, k, v, q_segment_ids, kv_segment_ids, causal,
                      scale, block_q, block_k)
    return out


def _fas_fwd(q, k, v, q_seg, kv_seg, causal, scale, block_q, block_k):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t, lse = _flash_fwd(qt, kt, vt, q_seg, kv_seg, causal, scale,
                            block_q, block_k)
    out, lse = _kept(jnp.swapaxes(out_t, 1, 2), lse)
    return out, (q, k, v, q_seg, kv_seg, out, lse)


def _fas_bwd(causal, scale, block_q, block_k, res, g):
    q, k, v, q_seg, kv_seg, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t = jnp.swapaxes(out, 1, 2)
    do_t = jnp.swapaxes(g, 1, 2)
    dq_t, dk_t, dv_t = _flash_bwd(qt, kt, vt, out_t, lse, do_t, q_seg,
                                  kv_seg, causal, scale, block_q, block_k)
    dq = jnp.swapaxes(dq_t, 1, 2).astype(q.dtype)
    dk = jnp.swapaxes(dk_t, 1, 2).astype(k.dtype)
    dv = jnp.swapaxes(dv_t, 1, 2).astype(v.dtype)
    zseg = lambda s: np.zeros(s.shape, jax.dtypes.float0)
    return dq, dk, dv, zseg(q_seg), zseg(kv_seg)


flash_attention_pallas_segmented.defvjp(_fas_fwd, _fas_bwd)


# The names under which ``_fa_fwd`` and ``_fas_fwd`` hand out the forward
# kernel's output and its log-sum-exp. They are the residuals the backward
# rules read, so a rematerialised region that keeps them
# (``distributed.fleet.recompute(keep=FLASH_KEEP)``) does not run the
# forward kernel again. Outside such a region a name lowers to nothing.
# Below the rules, so that no kernel call site above moves a line (source
# locations are in the kernels' compile-cache key).
FLASH_KEEP = ("flash_out", "flash_lse")


def _kept(out, lse):
    from jax.ad_checkpoint import checkpoint_name
    return (checkpoint_name(out, FLASH_KEEP[0]),
            checkpoint_name(lse, FLASH_KEEP[1]))


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             block_q=DEFAULT_BLOCK_Q,
                             block_k=DEFAULT_BLOCK_K):
    """Raw forward returning (out, lse) — the ring-attention inner block
    (online-softmax merge across ring steps needs the lse). [b,s,h,d] in,
    out [b,s,h,d], lse [b,h,s]. Not differentiable; ring attention
    implements its own backward over the ring."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t, lse = _flash_fwd(qt, kt, vt, None, None, causal, scale,
                            block_q, block_k)
    return jnp.swapaxes(out_t, 1, 2), lse


def flash_attention_bwd_block(q, k, v, out, lse, do, causal=False,
                              scale=None, block_q=DEFAULT_BLOCK_Q,
                              block_k=DEFAULT_BLOCK_K):
    """Raw backward for one (q-shard, kv-shard) block given the MERGED lse
    — the ring-attention backward inner step. Layouts as
    flash_attention_with_lse; returns (dq, dk, dv) with dk/dv float32
    [b, s, hk, d]."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out_t = jnp.swapaxes(out, 1, 2)
    do_t = jnp.swapaxes(do, 1, 2)
    dq_t, dk_t, dv_t = _flash_bwd(qt, kt, vt, out_t, lse, do_t, None, None,
                                  causal, scale, block_q, block_k)
    return (jnp.swapaxes(dq_t, 1, 2), jnp.swapaxes(dk_t, 1, 2),
            jnp.swapaxes(dv_t, 1, 2))
