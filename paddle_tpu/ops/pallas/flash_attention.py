"""Flash attention, Pallas TPU implementation (fwd + bwd), with optional
segment-ids (varlen/packed-sequence) masking.

Replaces the reference's third_party/flashattn CUDA kernels
(/root/reference/paddle/phi/kernels/gpu/flash_attn_kernel.cu; varlen API
/root/reference/python/paddle/nn/functional/flash_attention.py:302).
Blocked online-softmax over KV tiles; LSE saved for the backward; causal
masking with early loop exit.

GQA is handled WITHOUT expanding K/V in HBM: forward and dq kernels read
the shared kv-head block via index maps (hi // group), and the dk/dv
kernel walks the kv head's query group in its innermost grid dimension,
gathering the group's sum in VMEM — no jnp.repeat, no group-expanded HBM
traffic.

Segment ids (int32, [batch, seq]) restrict attention to tokens of equal
id — the packed-sequence ("varlen"/"unpadded") training path. Negative or
mismatched ids are fully masked; fully-masked query rows produce zero
output (guarded online softmax, not NaN).

A window (``window`` keys, static; causal attention only) restricts a
query at position t to the keys s with ``t - window < s <= t``: its own
key and the ``window - 1`` before it. The forward kernel then starts its
key loop at the band's first block, the backward kernels list only the
band's blocks among their steps, and a tile the band's lower edge cuts is
masked beside the causal compare, so tiles outside the band are neither
fetched nor computed; such calls are named ``flash_win_*``.
``window=None`` is the program without any of it.

Layout contract (paddle convention at the API): q/k/v [batch, seq, heads,
head_dim]; kernels internally run [batch, heads, seq, head_dim]. On the
v5e head_dim 64 compiles and reads 21.5% of peak, 128 reads 38.9% (PR 34).

VMEM budget: the forward kernel holds K and V whole per (batch,
kv-head), and the pipeline buffers each twice: at seq 16k, d=128, bf16
that is 16 MB and past the 16 MB a kernel gets by default, so the call
asks for what it holds (``_vmem_room``; shapes that fit ask for nothing).
The backward pass is one dq call and one dk/dv call over the whole
sequence: each holds one block of its own side with its float32 gradient
and streams the other side in chunks of ``_STREAM_BLOCKS`` blocks, about
9 MB at d=128 whatever the length (``_flash_bwd``). Longer sequences
belong to ring attention (paddle_tpu.distributed.ring_attention) which
shards seq over the mesh.
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
from ...utils import telemetry


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


def _vmem_room(resident_bytes, semantics=None):
    """``pallas_call`` options for a call whose grid has ``semantics``
    (None: Pallas's default) and that holds ``resident_bytes`` of operand
    blocks, which the pipeline buffers twice: no VMEM limit while they
    fit what a kernel gets by default, else one that holds them."""
    need = 2 * resident_bytes + (8 << 20)
    params = {} if semantics is None else {"dimension_semantics": semantics}
    if need > 16 << 20:
        params["vmem_limit_bytes"] = need
    return {"compiler_params": pltpu.CompilerParams(**params)} if params \
        else {}


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


# The backward pass is one dq call and one dk/dv call over the whole
# sequence. Each keeps one block of its own side in VMEM (dq a block of
# queries, dk/dv a block of keys) and streams the other side through its
# grid, this many blocks a step (8,192 rows at the blocks of 512). On the
# v5e a step costs about a third of a microsecond besides its work, so
# fewer, longer steps win: the SmallThinker layer's backward pass read
# 52.4 / 49.0 / 46.7 ms at 4 / 8 / 16 blocks a step, a window layer's
# 27.4 / 26.0 / 24.8 (1 x 16,384, 28 q / 4 kv heads of 128, bf16).
_STREAM_BLOCKS = 16

# A grid step's fields in the table ``_bwd_steps`` makes: the kept block
# (its row) and the chunk of the streamed side it meets; the chunk's
# blocks [lo, hi) to walk, of which no mask cuts those in [plain_lo,
# plain_hi); whether the step is its row's first and its last.
_ROW, _CHUNK, _LO, _PLAIN_LO, _PLAIN_HI, _HI, _FIRST, _LAST = range(8)
_FIELDS = 8


def _chunk_blocks(n):
    """Blocks a backward step streams in: the most, up to
    ``_STREAM_BLOCKS``, that divide the side's ``n`` blocks."""
    return next(c for c in range(min(_STREAM_BLOCKS, n), 0, -1)
                if n % c == 0)


def _dq_spans(nq, nk, bq, bk, base, causal, window):
    """For each block of queries, (lo, hi, a, b): it sees keys in the key
    blocks [lo, hi), and no mask cuts the tiles of those in [a, b)."""
    spans = []
    for i in range(nq):
        first = base + i * bq          # the block's global positions
        last = first + bq - 1
        lo, hi, a, b = 0, nk, 0, nk
        if causal:
            hi = last // bk + 1        # up to the key at `last`
            b = (first + 1) // bk      # keys at or before `first`
        if window is not None:
            lo = (first - window + 1) // bk      # the first key `first` sees
            a = -(-(last - window + 1) // bk)    # keys `last` sees
        spans.append(tuple(min(max(x, 0), nk) for x in (lo, hi, a, b)))
    return spans


def _dkv_spans(nk, nq, bk, bq, base, causal, window):
    """For each block of keys, (lo, hi, a, b): queries in the query blocks
    [lo, hi) see it, and no mask cuts the tiles of those in [a, b)."""
    spans = []
    for j in range(nk):
        first = j * bk - base          # the block's keys as query positions
        last = first + bk - 1
        lo, hi, a, b = 0, nq, 0, nq
        if causal:
            lo = first // bq           # from the query at `first`
            a = -(-last // bq)         # queries at or after `last`
        if window is not None:
            hi = (last + window - 1) // bq + 1   # the last query `last` has
            b = (first + window - bq) // bq + 1  # queries that see `first`
        spans.append(tuple(min(max(x, 0), nq) for x in (lo, hi, a, b)))
    return spans


def _bwd_steps(spans, cb):
    """The grid steps of one backward call as a flat int32 table,
    ``_FIELDS`` a step: each row walks the chunks of ``cb`` blocks that
    meet its span, in order, and nothing else; a row that sees nothing is
    one step that walks no block, so that its gradient is still written
    (zeros)."""
    table = []
    for row, (lo, hi, a, b) in enumerate(spans):
        chunks = range(lo // cb, (hi - 1) // cb + 1) if hi > lo else [0]
        for n, c in enumerate(chunks):
            c_lo, c_hi = (max(lo, c * cb), min(hi, c * cb + cb)) \
                if hi > lo else (0, 0)
            p_lo = min(max(a, c_lo), c_hi)
            p_hi = min(max(b, p_lo), c_hi)
            table += [row, c, c_lo, p_lo, p_hi, c_hi, n == 0,
                      n == len(chunks) - 1]
    return np.asarray(table, np.int32)


def _mask(s, queries, keys, causal, window):
    """Scores ``s`` of query positions ``queries`` against key positions
    ``keys`` under the causal compare and the band's lower edge."""
    if causal:
        s = jnp.where(queries >= keys, s, _NEG_INF)
    if window is not None:
        s = jnp.where(keys > queries - window, s, _NEG_INF)
    return s


def _walk(steps, t, tile, carry, causal, window):
    """``tile(block, carry, mask)`` over the blocks step ``t`` walks, in
    order: ``mask`` applies ``_mask`` to a tile the causal diagonal or the
    band's lower edge cuts, and is None for the others."""
    at = lambda f: steps[_FIELDS * t + f]
    if not causal and window is None:
        return jax.lax.fori_loop(at(_LO), at(_HI),
                                 functools.partial(tile, mask=None), carry)
    mask = functools.partial(_mask, causal=causal, window=window)
    for lo, hi, cut in ((_LO, _PLAIN_LO, mask), (_PLAIN_LO, _PLAIN_HI, None),
                        (_PLAIN_HI, _HI, mask)):
        carry = jax.lax.fori_loop(at(lo), at(hi),
                                  functools.partial(tile, mask=cut), carry)
    return carry


def _bwd_dq_kernel(steps, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, scale, causal, bq, bk, cb, base, segmented,
                   window=None):
    """Grid (b, h, steps): a block of queries against a chunk of ``cb``
    key blocks. The block's dq gathers in a float32 scratch over its
    steps and is written once, at its last."""
    if segmented:
        qseg_ref, kseg_ref, dq_ref, acc = refs
    else:
        dq_ref, acc = refs
    t = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)                     # [bq, d]
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0, 0][:, None]                         # [bq, 1]
    delta = delta_ref[0, 0, 0][:, None]
    q_first = base + steps[_FIELDS * t + _ROW] * bq        # global
    k_chunk = steps[_FIELDS * t + _CHUNK] * cb
    if segmented:
        qseg = qseg_ref[0]

    def tile(kj, dq, mask):
        off = (kj - k_chunk) * bk
        k_blk = k_ref[0, 0, pl.ds(off, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(off, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = mask(s, q_first + jax.lax.broadcasted_iota(jnp.int32,
                                                          s.shape, 0),
                     kj * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                        s.shape, 1))
        if segmented:
            kseg = kseg_ref[0, pl.ds(off, bk)]
            s = jnp.where(qseg[:, None] == kseg[None, :], s, _NEG_INF)
        p = jnp.where(s > _NEG_INF * 0.5,
                      jnp.exp(s - lse), 0.0)                 # [bq, bk]
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                        # [bq, bk]
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(steps[_FIELDS * t + _FIRST] == 1)
    def _init():
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

    acc[...] = _walk(steps, t, tile, acc[...], causal, window)

    @pl.when(steps[_FIELDS * t + _LAST] == 1)
    def _write():
        dq_ref[0, 0] = acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(steps, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, scale, causal, bq, bk, cb, base, group,
                    segmented, window=None):
    """Grid (b, hk, steps, group): a block of keys against a chunk of
    ``cb`` query blocks of one query head of its group (GQA without
    expanding K/V), so the block's dk and dv gather over its steps and
    its group's heads in float32 scratch and are written once, at the
    last. A tile is computed transposed, keys down and queries across:
    lse and delta are then rows as they are stored, and dv, dk plain
    products."""
    if segmented:
        qseg_ref, kseg_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
    t, gi = pl.program_id(2), pl.program_id(3)
    k_blk = k_ref[0, 0].astype(jnp.float32)                  # [bk, d]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    k_first = steps[_FIELDS * t + _ROW] * bk                 # global
    q_chunk = steps[_FIELDS * t + _CHUNK] * cb
    if segmented:
        kseg = kseg_ref[0][:, None]                          # [bk, 1]

    def tile(qi, carry, mask):
        dk, dv = carry
        off = (qi - q_chunk) * bq
        q = q_ref[0, 0, pl.ds(off, bq), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.ds(off, bq), :].astype(jnp.float32)
        lse = lse_ref[0, 0, :, pl.ds(off, bq)]               # [1, bq]
        delta = delta_ref[0, 0, :, pl.ds(off, bq)]
        s = jax.lax.dot_general(k_blk, q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = mask(s, base + qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1),
                k_first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        if segmented:
            qseg = qseg_ref[:, pl.ds(off, bq)]               # [1, bq]
            s = jnp.where(kseg == qseg, s, _NEG_INF)
        p = jnp.where(s > _NEG_INF * 0.5,
                      jnp.exp(s - lse), 0.0)                 # [bk, bq]
        dv = dv + jax.lax.dot_general(p, do, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v_blk, do, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(ds, q, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    @pl.when((steps[_FIELDS * t + _FIRST] == 1) & (gi == 0))
    def _init():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    dk, dv = _walk(steps, t, tile, (dk_acc[...], dv_acc[...]), causal,
                   window)
    dk_acc[...] = dk
    dv_acc[...] = dv

    @pl.when((steps[_FIELDS * t + _LAST] == 1) & (gi == group - 1))
    def _write():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, q_seg, kv_seg, causal, scale,
               block_q, block_k, window=None, dkv_dtype=jnp.float32):
    """q/do [b,h,sq,d]; k/v [b,hk,sk,d] (NOT expanded); lse [b,h,sq].
    Returns dq [b,h,sq,d] in q's dtype and dk/dv [b,hk,sk,d], summed over
    each kv head's query group, in ``dkv_dtype``.

    One dq call and one dk/dv call, each over the whole sequence. dq keeps
    a block of queries with its do, lse and delta and streams K and V;
    dk/dv keeps a block of keys with its V and streams q, do, lse and
    delta of each query head of the group. A step brings in a chunk of
    ``_STREAM_BLOCKS`` blocks; the steps are listed at trace time
    (``_bwd_steps``) and reach the index maps as a prefetched table, so
    only chunks that hold a visible pair are fetched and, of those, only
    such blocks are walked. VMEM holds the kept block, its float32
    gradient and two buffers of each streamed chunk: about 9 MB at d=128
    and blocks of 512, whatever the length."""
    b, h, sq, d = q.shape
    hk, sk = k.shape[1], k.shape[2]
    group = h // hk
    bq, bk = min(block_q, sq), min(block_k, sk)
    nq, nk = pl.cdiv(sq, bq), pl.cdiv(sk, bk)
    base = sk - sq     # causal aligns queries to the END of the keys
    segmented = q_seg is not None
    # lse and delta as rows [b, h, 1, sq]: a block of either is lane-dense
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)[:, :, None]
    lse = lse[:, :, None]
    names = _names(window)
    calls = telemetry.default_tracer().metrics

    # dq: grid (b, h, steps); a step's row is a query block, its chunk
    # ck keys
    cb = _chunk_blocks(nk)
    ck = cb * bk
    steps = _bwd_steps(_dq_spans(nq, nk, bq, bk, base, causal, window), cb)

    def q_rows(bi, hi, t, st):
        return (bi, hi, st[_FIELDS * t + _ROW], 0)

    def kv_chunk(bi, hi, t, st):
        return (bi, hi // group, st[_FIELDS * t + _CHUNK], 0)

    def q_stats(bi, hi, t, st):
        return (bi, hi, 0, st[_FIELDS * t + _ROW])

    in_specs = [
        pl.BlockSpec((1, 1, bq, d), q_rows),
        pl.BlockSpec((1, 1, ck, d), kv_chunk),
        pl.BlockSpec((1, 1, ck, d), kv_chunk),
        pl.BlockSpec((1, 1, bq, d), q_rows),
        pl.BlockSpec((1, 1, 1, bq), q_stats),
        pl.BlockSpec((1, 1, 1, bq), q_stats),
    ]
    args = [q, k, v, do, lse, delta]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, bq), lambda bi, hi, t, st:
                         (bi, st[_FIELDS * t + _ROW])),
            pl.BlockSpec((1, ck), lambda bi, hi, t, st:
                         (bi, st[_FIELDS * t + _CHUNK])),
        ]
        args += [q_seg, kv_seg]
    calls.inc("attn.flash.bwd_calls")
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bk=bk, cb=cb, base=base, segmented=segmented,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, len(steps) // _FIELDS),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, d), q_rows),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=_sds((b, h, sq, d), q.dtype, q),
        **_vmem_room(2 * (ck + bq) * d * q.dtype.itemsize,
                     ("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=names[1],
    )(jnp.asarray(steps), *args)

    # dk/dv: grid (b, hk, steps, group); a step's row is a key block, its
    # chunk cq queries of query head hk_index * group + g
    cb = _chunk_blocks(nq)
    cq = cb * bq
    steps = _bwd_steps(_dkv_spans(nk, nq, bk, bq, base, causal, window), cb)

    def q_chunk(bi, hki, t, g, st):
        return (bi, hki * group + g, st[_FIELDS * t + _CHUNK], 0)

    def k_rows(bi, hki, t, g, st):
        return (bi, hki, st[_FIELDS * t + _ROW], 0)

    def q_chunk_stats(bi, hki, t, g, st):
        return (bi, hki * group + g, 0, st[_FIELDS * t + _CHUNK])

    in_specs = [
        pl.BlockSpec((1, 1, cq, d), q_chunk),
        pl.BlockSpec((1, 1, bk, d), k_rows),
        pl.BlockSpec((1, 1, bk, d), k_rows),
        pl.BlockSpec((1, 1, cq, d), q_chunk),
        pl.BlockSpec((1, 1, 1, cq), q_chunk_stats),
        pl.BlockSpec((1, 1, 1, cq), q_chunk_stats),
    ]
    args = [q, k, v, do, lse, delta]
    if segmented:
        in_specs += [
            pl.BlockSpec((1, cq), lambda bi, hki, t, g, st:
                         (bi, st[_FIELDS * t + _CHUNK])),
            pl.BlockSpec((1, bk), lambda bi, hki, t, g, st:
                         (bi, st[_FIELDS * t + _ROW])),
        ]
        args += [q_seg, kv_seg]
    calls.inc("attn.flash.bwd_calls")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, cb=cb, base=base, group=group,
                          segmented=segmented, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hk, len(steps) // _FIELDS, group),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, 1, bk, d), k_rows),
                       pl.BlockSpec((1, 1, bk, d), k_rows)],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                            pltpu.VMEM((bk, d), jnp.float32)]),
        out_shape=[_sds((b, hk, sk, d), dkv_dtype, q),
                   _sds((b, hk, sk, d), dkv_dtype, q)],
        **_vmem_room(2 * (cq + bk) * d * q.dtype.itemsize,
                     ("parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=_interpret(),
        name=names[2],
    )(jnp.asarray(steps), *args)
    return dq, dk, dv


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
                                  causal, scale, block_q, block_k, window,
                                  dkv_dtype=k.dtype)
    dq = jnp.swapaxes(dq_t, 1, 2)
    dk = jnp.swapaxes(dk_t, 1, 2)
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
                                  kv_seg, causal, scale, block_q, block_k,
                                  dkv_dtype=k.dtype)
    dq = jnp.swapaxes(dq_t, 1, 2)
    dk = jnp.swapaxes(dk_t, 1, 2)
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
