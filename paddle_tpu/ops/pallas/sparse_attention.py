"""Learned sparse attention (the DeepSeek-Sparse-Attention scheme), Pallas
TPU kernels and the differentiable entry point over them.

A light *indexer* scores every causal (query, key) pair,

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        (s <= t)

each query keeps the ``topk`` keys with the largest score (exact; ties go
to the lower index), the main attention runs over the kept keys alone,
and the indexer is trained by its own loss: the KL divergence from the
main attention's probabilities over the kept keys (summed over the heads
and normalised) to the softmax of the indexer's scores over the same
keys. The selection is carried as a dense int8 mask [batch, seq, seq], so
the attention kernels are flash kernels with one more operand: what they
compute is exact, what they cost is the causal triangle.

Kernels (each ``pallas_call`` is named; a device trace shows the name):

``indexer_scores``      I as float32, bf16 operands, float32 accumulation
``topk_select``         I -> mask: a radix select on the scores' bits, 32
                        counting passes for the ``topk``-th largest value
                        of a row and ``log2(seq)`` more for the ties
``sparse_attn_fwd``     online-softmax attention under the mask, a step a
                        query tile and head, its key blocks in a loop
``indexer_loss_rows``   the heads' summed probabilities under the mask (the
                        indexer's target) folded with its scores into the
                        loss's sums per row, a step a causal tile, its
                        heads in a loop
``sparse_attn_bwd_dq``  dq, a step a query tile and head, its key blocks
                        in a loop
``sparse_attn_bwd_dkv`` dk and dv, a step a causal tile, a key tile's
                        query tiles in order, its heads in a loop; the
                        same probabilities gather into the target once
                        more and the tile ends in the loss's gradient
                        with respect to the scores

No grid step of the four lies above the diagonal; each call adds its
steps to the trace-time counter ``attn.sparse.grid_steps``.

``learned_sparse_attention`` ties them together under one ``custom_vjp``:
it keeps q, k, v, the output, the row statistics, the mask and the
indexer's operands, and makes the scores and the target again in the
backward pass, so that no float32 [seq, seq] array outlives its pass
(the target never leaves VMEM). A step makes the main attention's q.k^T
and ``exp`` over the causal tiles four times: in the forward kernel, in
``indexer_loss_rows`` (which needs every head's finished row statistic),
in dq and in dkv.
The indexer's operands get their gradient from the indexer's loss alone
and q, k, v from the output alone. Every piece has a plain ``jax.numpy``
twin below (``*_reference``) that the tests hold it to.

Layouts: q [b, h, s, d]; k, v [b, hk, s, d]; qI [b, hi, s, di];
kI [b, s, di]; w [b, s, hi] float32 with the score's scales folded in.
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

_NEG = -1e30
_INT_MIN = -2 ** 31
F32 = jnp.float32


def _block(seq: int, want: int) -> int:
    b = min(want, seq)
    if seq % b:
        raise ValueError(f"sequence length {seq} is not a multiple of the "
                         f"kernel's block {b}")
    return b


def _params(*semantics, **more):
    return pltpu.CompilerParams(dimension_semantics=semantics, **more)


# ---------------------------------------------------------------------------
# indexer scores
# ---------------------------------------------------------------------------

def _scores_kernel(q_ref, k_ref, w_ref, o_ref, *, heads, bq, bk):
    qi, kj = pl.program_id(1), pl.program_id(2)
    live = kj * bk <= qi * bq + bq - 1      # the tile holds a causal pair

    @pl.when(live)
    def _():
        k = k_ref[0]                                    # [bk, di]
        w = w_ref[0]                                    # [bq, hi] f32
        acc = jnp.zeros((bq, bk), F32)
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[0, j], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[0] = jnp.zeros((bq, bk), F32)


def indexer_scores(qi, ki, w, block_q=256, block_k=512):
    """[b, s, s] float32 scores; tiles above the diagonal are zeros and
    mean nothing (the selection applies the causal bound)."""
    b, hi, s, di = qi.shape
    bq, bk = _block(s, block_q), _block(s, block_k)

    def last(q_i):
        return (q_i * bq + bq - 1) // bk

    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=hi, bq=bq, bk=bk),
        grid=(b, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, hi, bq, di), lambda bi, q_i, kj: (bi, 0, q_i, 0)),
            pl.BlockSpec((1, bk, di), lambda bi, q_i, kj:
                         (bi, jnp.minimum(kj, last(q_i)), 0)),
            pl.BlockSpec((1, bq, hi), lambda bi, q_i, kj: (bi, q_i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, bk), lambda bi, q_i, kj: (bi, q_i, kj)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), F32),
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="indexer_scores",
    )(qi, ki, w)


def indexer_scores_reference(qi, ki, w):
    s = jnp.einsum("bjqd,bsd->bjqs", qi.astype(F32), ki.astype(F32),
                   precision="highest")
    return jnp.einsum("bjqs,bqj->bqs", jnp.maximum(s, 0.0), w.astype(F32),
                      precision="highest")


def _indexer_scores_bwd(qi, ki, w, d_scores, chunk=512):
    """Gradients of the scores w.r.t. the indexer's operands from the
    cotangent [b, s, s] (zero outside the selection), one block of
    queries against its causal keys at a time. Left to XLA."""
    b, hi, s, di = qi.shape
    c = min(chunk, s)
    dq, dw = [], []
    dk = jnp.zeros((b, s, di), F32)
    for q0 in range(0, s, c):
        e = q0 + c                                      # causal extent
        qc, kc, wc = qi[:, :, q0:e], ki[:, :e], w[:, q0:e]
        sc = jnp.einsum("bjqd,bsd->bjqs", qc, kc,
                        preferred_element_type=F32)
        dc = d_scores[:, q0:e, :e]
        dw.append(jnp.einsum("bjqs,bqs->bqj", jnp.maximum(sc, 0.0), dc))
        gs = (jnp.where(sc > 0, dc[:, None], 0.0)
              * jnp.swapaxes(wc, 1, 2)[..., None]).astype(qi.dtype)
        dq.append(jnp.einsum("bjqs,bsd->bjqd", gs, kc,
                             preferred_element_type=F32))
        dk = dk.at[:, :e].add(jnp.einsum("bjqs,bjqd->bsd", gs, qc,
                                         preferred_element_type=F32))
    return (jnp.concatenate(dq, 2).astype(qi.dtype), dk.astype(ki.dtype),
            jnp.concatenate(dw, 1).astype(w.dtype))


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------

def _select_kernel(i_ref, m_ref, *, topk, rows, seq):
    r0 = pl.program_id(1) * rows
    x = i_ref[0]
    x = jnp.where(x == 0.0, 0.0, x)          # -0.0 and 0.0 are one score
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    # a key whose signed order is the floats' order
    key = bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF))
    row = r0 + jax.lax.broadcasted_iota(jnp.int32, (rows, seq), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, seq), 1)
    causal = col <= row
    key = jnp.where(causal, key, jnp.int32(_INT_MIN))

    def count(pred):
        return jnp.sum(pred.astype(F32), axis=1, keepdims=True)

    # the largest thr with at least topk keys >= thr, bit by bit from the
    # top; the first step's INT_MIN + INT_MIN wraps to 0, the middle
    def value_bit(i, thr):
        cand = thr + (jnp.int32(1) << (31 - i))
        return jnp.where(count(key >= cand) >= topk, cand, thr)
    thr = jax.lax.fori_loop(0, 32, value_bit,
                            jnp.full((rows, 1), _INT_MIN, jnp.int32))
    above, tie = key > thr, key == thr
    need = topk - count(above)               # ties to take, lowest first
    nbits = max(1, (seq - 1).bit_length())

    # the largest m with fewer than `need` ties before column m: that
    # column holds the last tie taken
    def index_bit(i, m):
        cand = m + (jnp.int32(1) << (nbits - 1 - i))
        return jnp.where(count(tie & (col < cand)) < need, cand, m)
    m = jax.lax.fori_loop(0, nbits, index_bit,
                          jnp.zeros((rows, 1), jnp.int32))
    keep = causal & (above | (tie & (col <= m)))
    m_ref[0] = keep.astype(jnp.int8)


def topk_select(scores, topk, block_rows=32):
    """int8 mask [b, s, s]: 1 where key s is among the min(t + 1, topk)
    best-scored keys s <= t of query t, ties to the lower index."""
    b, s, _ = scores.shape
    rows = _block(s, block_rows)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=int(topk), rows=rows, seq=s),
        grid=(b, s // rows),
        in_specs=[pl.BlockSpec((1, rows, s), lambda bi, r: (bi, r, 0))],
        out_specs=pl.BlockSpec((1, rows, s), lambda bi, r: (bi, r, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.int8),
        compiler_params=_params("parallel", "parallel"),
        interpret=_interpret(),
        name="topk_select",
    )(scores)


def topk_select_reference(scores, topk):
    """The same set through ``lax.top_k`` (which puts the lower index
    first among equal values)."""
    b, s, _ = scores.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    x = jnp.where(causal, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(x, min(int(topk), s))
    hit = jnp.zeros((b, s, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
        idx].set(True)
    return (hit & causal).astype(jnp.int8)


# ---------------------------------------------------------------------------
# attention under the mask
# ---------------------------------------------------------------------------
#
# The four kernels walk the causal tiles alone, in few long grid steps.
# ``sparse_attn_fwd`` and ``sparse_attn_bwd_dq`` take one query tile of one
# head a step (grid (b, nq, h), the heads innermost) and loop over its key
# blocks up to the diagonal's inside the step: K and V of the head's key
# head and the tile's mask row stay in VMEM, the mask row over all heads.
# ``indexer_loss_rows`` and ``sparse_attn_bwd_dkv`` need every head of a
# tile before its epilogue, so they take one causal tile a step and loop
# over the heads inside it; their steps are listed at trace time
# (``_causal_steps``) and reach the index maps as a prefetched table.
# Every head's row statistics (lse, delta) travel as one [b, s, h] array,
# a query tile's block of which serves all heads: a [.., s, 1] statistic
# pads its one lane to 128, in VMEM and in HBM.

# A prefetched step's fields: its query tile, its key tile, whether it is
# the first of its row (the query tile's in ``indexer_loss_rows``, the key
# tile's in ``sparse_attn_bwd_dkv``)
_QT, _KT, _FIRST = range(3)
_FIELDS = 3


def _field(steps, t, f):
    return steps[_FIELDS * t + f]


def _last_k(q_i, bq, bk):
    """The last key tile that query tile ``q_i`` needs."""
    return (q_i * bq + bq - 1) // bk


def _causal_steps(s, bq, bk, by_key):
    """The causal tiles as a flat int32 table, ``_FIELDS`` a step: by
    query tile with its key tiles innermost, or (``by_key``) by key tile
    with its query tiles innermost."""
    nq, nk = s // bq, s // bk
    table = []
    if by_key:
        for kj in range(nk):
            first = kj * bk // bq
            table += [[q_i, kj, q_i == first] for q_i in range(first, nq)]
    else:
        for q_i in range(nq):
            table += [[q_i, kj, kj == 0]
                      for kj in range(_last_k(q_i, bq, bk) + 1)]
    return np.asarray(table, np.int32).reshape(-1)


def _padded_bytes(shape, dtype):
    """VMEM bytes of a block: its last dimension in lanes of 128, the one
    before it in sublane tiles (8 rows of 32 bits, packed narrower)."""
    itemsize = jnp.dtype(dtype).itemsize
    *lead, rows, lanes = shape
    sub = 8 * 4 // itemsize
    return (math.prod(lead) * -(-rows // sub) * sub * -(-lanes // 128) * 128
            * itemsize)


def _compiler_params(semantics, blocks, scratch, tile):
    """``pallas_call`` options that ask for the VMEM a call holds: two
    pipeline buffers of each block in ``blocks`` ((shape, dtype) pairs),
    the ``scratch`` bytes, and eight float32 temporaries of a ``tile``
    (scores, probabilities, their gradient, the mask's compare, ...)."""
    need = (2 * sum(_padded_bytes(*b) for b in blocks) + scratch
            + 8 * _padded_bytes(tile, F32))
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=need)


def _count_steps(n):
    telemetry.default_tracer().metrics.inc("attn.sparse.grid_steps", n)


def _rows(stat):
    """A [b, h, s, 1] statistic as [b, s, h]: the kernels' layout."""
    return jnp.swapaxes(stat[..., 0], 1, 2)


def _column(rows, hi):
    """Head ``hi``'s column [bq, 1] of a block of rows [bq, h]: a lane
    picked by compare and summed, exact."""
    lane = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 1)
    return jnp.sum(jnp.where(lane == hi, rows, 0.0), axis=1, keepdims=True)


def _probs(q, k, keep, scale, row_stat):
    """(masked scores' probabilities given the rows' statistic) of one
    tile: exp(q k^T * scale - row_stat) where kept, else 0."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale
    return jnp.where(keep, jnp.exp(s - row_stat), 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_sc, l_sc, acc_sc, *, scale, bq, bk):
    """One query tile of one head: an online softmax over the key blocks
    up to the diagonal's, in order. The tile's log-sum-exp goes into its
    head's lane of the rows block, which stays in VMEM over the heads."""
    q_i, hi = pl.program_id(1), pl.program_id(2)
    q = q_ref[0, 0]
    m_sc[...] = jnp.full(m_sc.shape, _NEG, F32)
    l_sc[...] = jnp.zeros(l_sc.shape, F32)
    acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    def block(kj, carry):
        keys = pl.ds(pl.multiple_of(kj * bk, bk), bk)
        keep = mask_ref[0, :, keys].astype(jnp.int32) > 0
        s = jax.lax.dot_general(q, k_ref[0, 0, keys], (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * scale
        s = jnp.where(keep, s, _NEG)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, 0, keys], (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_sc[...] = m_new
        return carry

    jax.lax.fori_loop(0, _last_k(q_i, bq, bk) + 1, block, 0)
    l = jnp.maximum(l_sc[...], 1e-30)
    o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, lse_ref.shape[1:], 1)
    lse_ref[0] = jnp.where(lane == hi, m_sc[...] + jnp.log(l), lse_ref[0])


def sparse_attn_fwd(q, k, v, mask, scale, block_q=512, block_k=512):
    """(out [b, h, s, d], lse [b, h, s, 1] float32)."""
    b, h, s, d = q.shape
    g = h // k.shape[1]
    bq, bk = _block(s, block_q), _block(s, block_k)
    grid = (b, s // bq, h)
    _count_steps(math.prod(grid))
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, q_i, hi: (bi, hi, q_i, 0))
    kv_spec = pl.BlockSpec((1, 1, s, d), lambda bi, q_i, hi:
                           (bi, hi // g, 0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, bq=bq, bk=bk),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec,
                  pl.BlockSpec((1, bq, s), lambda bi, q_i, hi: (bi, q_i, 0))],
        out_specs=[q_spec,
                   pl.BlockSpec((1, bq, h), lambda bi, q_i, hi: (bi, q_i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b, s, h), F32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), F32), pltpu.VMEM((bq, 1), F32),
                        pltpu.VMEM((bq, d), F32)],
        # at the Keye widths (s 8192, blocks of 512, d 128): K and V of a
        # key head (2 x 2 MiB) and the mask row (4 MiB), buffered twice,
        # 16 MiB of the 25.75 MiB asked; eight float32 tiles 8 MiB
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary"),
            [((bq, d), q.dtype), ((s, d), k.dtype), ((s, d), v.dtype),
             ((bq, s), mask.dtype), ((bq, d), q.dtype), ((bq, h), F32)],
            2 * _padded_bytes((bq, 1), F32) + _padded_bytes((bq, d), F32),
            (bq, bk)),
        interpret=_interpret(),
        name="sparse_attn_fwd",
    )(q, k, v, mask)
    return out, jnp.swapaxes(lse, 1, 2)[..., None]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   mask_ref, dq_ref, acc_sc, *, scale, bq, bk):
    """One query tile of one head: dq over the key blocks up to the
    diagonal's, in order, written once."""
    q_i, hi = pl.program_id(1), pl.program_id(2)
    q, do = q_ref[0, 0], do_ref[0, 0]
    lse, delta = _column(lse_ref[0], hi), _column(delta_ref[0], hi)
    acc_sc[...] = jnp.zeros(acc_sc.shape, F32)

    def block(kj, carry):
        keys = pl.ds(pl.multiple_of(kj * bk, bk), bk)
        keep = mask_ref[0, :, keys].astype(jnp.int32) > 0
        k = k_ref[0, 0, keys]
        p = _probs(q, k, keep, scale, lse)
        dp = jax.lax.dot_general(do, v_ref[0, 0, keys],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        ds = p * (dp - delta) * scale
        acc_sc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        return carry

    jax.lax.fori_loop(0, _last_k(q_i, bq, bk) + 1, block, 0)
    dq_ref[0, 0] = acc_sc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(steps, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    mask_ref, sc_ref, z_ref, lsei_ref,
                    dk_ref, dv_ref, ds_ref, tgt,
                    *, scale, bq, heads, group):
    """One causal (key tile, query tile) of dk and dv, the heads in a loop,
    and of the indexer's side of the same tile: every head's probabilities
    are made once, feed dk / dv of the head's key head (the output blocks
    hold all key heads and stay in VMEM over a key tile's query tiles) and
    gather, heads summed, in ``tgt``. After the last head the tile's target
    meets the indexer's scores: the loss's gradient with respect to the
    scores, softmax(scores) - target / z over the kept keys, times the
    number of queries, into the key tile's column, which stays in VMEM
    and is written whole: zeros above the tile's first causal query
    tile."""
    t = pl.program_id(1)
    q_i = _field(steps, t, _QT)

    @pl.when(_field(steps, t, _FIRST) == 1)
    def _():
        dk_ref[...] = jnp.zeros(dk_ref.shape, F32)
        dv_ref[...] = jnp.zeros(dv_ref.shape, F32)

        def dead(i, carry):
            ds_ref[0, pl.ds(pl.multiple_of(i * bq, bq), bq)] = jnp.zeros(
                (bq, ds_ref.shape[2]), F32)
            return carry
        jax.lax.fori_loop(0, q_i, dead, 0)

    keep = mask_ref[0].astype(jnp.int32) > 0
    lse, delta = lse_ref[0], delta_ref[0]
    tgt[...] = jnp.zeros(tgt.shape, F32)

    def head(hi, carry):
        kh = hi // group
        q, do = q_ref[0, hi], do_ref[0, hi]
        p = _probs(q, k_ref[0, kh], keep, scale, _column(lse, hi))
        tgt[...] += p
        dv_ref[0, kh] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=F32)
        dp = jax.lax.dot_general(do, v_ref[0, kh], (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        ds = p * (dp - _column(delta, hi)) * scale
        dk_ref[0, kh] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=F32)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)
    soft = jnp.where(keep, jnp.exp(sc_ref[0] - lsei_ref[0]), 0.0)
    # the heads' mean: the division once a tile, not once a head
    ds_ref[0, pl.ds(pl.multiple_of(q_i * bq, bq), bq)] = \
        soft - tgt[...] * (1.0 / heads) / z_ref[0]


def sparse_attn_bwd(q, k, v, out, lse, do, mask, scores, rows, scale,
                    block_q=512, block_k=512):
    """(dq in q's dtype; dk, dv float32 summed over each key head's query
    heads; the indexer's loss's gradient with respect to ``scores``
    [b, s, s] float32, times the number of queries: softmax(scores) -
    target / z over the kept keys, zeros elsewhere). ``rows`` = (z,
    lse_i), each [b, s, 1], are ``indexer_loss``'s."""
    b, h, s, d = q.shape
    hk = k.shape[1]
    g = h // hk
    bq, bk = _block(s, block_q), _block(s, block_k)
    lse = _rows(lse)
    delta = jnp.swapaxes(jnp.sum(out.astype(F32) * do.astype(F32), -1), 1, 2)

    grid = (b, s // bq, h)
    _count_steps(math.prod(grid))
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, q_i, hi: (bi, hi, q_i, 0))
    kv_spec = pl.BlockSpec((1, 1, s, d), lambda bi, q_i, hi:
                           (bi, hi // g, 0, 0))
    row_spec = pl.BlockSpec((1, bq, h), lambda bi, q_i, hi: (bi, q_i, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, bq=bq, bk=bk),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec,
                  pl.BlockSpec((1, bq, s), lambda bi, q_i, hi: (bi, q_i, 0))],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), F32)],
        # at the Keye widths: K and V of a key head (2 x 2 MiB) and the
        # mask row (4 MiB), buffered twice, 16 MiB of the 26 MiB asked;
        # eight float32 tiles 8 MiB
        compiler_params=_compiler_params(
            ("parallel", "parallel", "parallel"),
            [((bq, d), q.dtype), ((s, d), k.dtype), ((s, d), v.dtype),
             ((bq, d), do.dtype), ((bq, h), F32), ((bq, h), F32),
             ((bq, s), mask.dtype), ((bq, d), q.dtype)],
            _padded_bytes((bq, d), F32), (bq, bk)),
        interpret=_interpret(),
        name="sparse_attn_bwd_dq",
    )(q, k, v, do, lse, delta, mask)

    # the causal tiles by key tile, each with all heads of its query tile
    steps = _causal_steps(s, bq, bk, by_key=True)
    grid = (b, len(steps) // _FIELDS)
    _count_steps(math.prod(grid))
    heads_spec = pl.BlockSpec((1, h, bq, d), lambda bi, t, st:
                              (bi, 0, _field(st, t, _QT), 0))
    keys_spec = pl.BlockSpec((1, hk, bk, d), lambda bi, t, st:
                             (bi, 0, _field(st, t, _KT), 0))
    row_spec = pl.BlockSpec((1, bq, h), lambda bi, t, st:
                            (bi, _field(st, t, _QT), 0))
    tile_spec = pl.BlockSpec((1, bq, bk), lambda bi, t, st:
                             (bi, _field(st, t, _QT), _field(st, t, _KT)))
    stat_spec = pl.BlockSpec((1, bq, 1), lambda bi, t, st:
                             (bi, _field(st, t, _QT), 0))
    dk, dv, d_scores = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, bq=bq, heads=h,
                          group=g),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[heads_spec, keys_spec, keys_spec, heads_spec, row_spec,
                      row_spec, tile_spec, tile_spec, stat_spec, stat_spec],
            out_specs=[keys_spec, keys_spec,
                       pl.BlockSpec((1, s, bk), lambda bi, t, st:
                                    (bi, 0, _field(st, t, _KT)))],
            scratch_shapes=[pltpu.VMEM((bq, bk), F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hk, s, d), F32),
                   jax.ShapeDtypeStruct((b, hk, s, d), F32),
                   jax.ShapeDtypeStruct((b, s, s), F32)],
        # at the Keye widths: the key tile's column of the loss's
        # gradient (16 MiB) and q and do of all 32 heads (2 x 4 MiB),
        # buffered twice, 48 MiB of the 67.5 MiB asked (the v5e has 128);
        # dk and dv 2 x 2 x 1 MiB, eight float32 tiles and ``tgt`` 9 MiB
        compiler_params=_compiler_params(
            ("parallel", "arbitrary"),
            [((h, bq, d), q.dtype), ((hk, bk, d), k.dtype),
             ((hk, bk, d), v.dtype), ((h, bq, d), do.dtype), ((bq, h), F32),
             ((bq, h), F32), ((bq, bk), mask.dtype), ((bq, bk), F32),
             ((bq, 1), F32), ((bq, 1), F32), ((hk, bk, d), F32),
             ((hk, bk, d), F32), ((s, bk), F32)],
            _padded_bytes((bq, bk), F32), (bq, bk)),
        interpret=_interpret(),
        name="sparse_attn_bwd_dkv",
    )(jnp.asarray(steps), q, k, v, do, lse, delta, mask, scores, *rows)
    return dq, dk, dv, d_scores


def _loss_kernel(steps, q_ref, k_ref, lse_ref, mask_ref, sc_ref,
                 a_ref, z_ref, m_ref, l_ref, tgt, *, scale, heads, group):
    """One causal (query tile, key tile) of the indexer's loss, the heads
    in a loop. The heads' mean probability under the mask (the target,
    each head's row summing to 1) gathers in VMEM; after the last head
    the tile is folded, together with the indexer's scores, into four
    sums per row: what the loss needs. The row's sums stay in VMEM over
    its key tiles."""
    bq = tgt.shape[0]

    @pl.when(_field(steps, pl.program_id(1), _FIRST) == 1)
    def _():
        a_ref[0] = jnp.zeros((bq, 1), F32)
        z_ref[0] = jnp.zeros((bq, 1), F32)
        l_ref[0] = jnp.zeros((bq, 1), F32)
        m_ref[0] = jnp.full((bq, 1), _NEG, F32)

    keep = mask_ref[0].astype(jnp.int32) > 0
    lse = lse_ref[0]
    tgt[...] = jnp.zeros(tgt.shape, F32)

    def head(hi, carry):
        tgt[...] += _probs(q_ref[0, hi], k_ref[0, hi // group], keep, scale,
                           _column(lse, hi)) * (1.0 / heads)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)
    t, sc = tgt[...], sc_ref[0]
    pos = keep & (t > 0.0)
    a_ref[0] += jnp.sum(jnp.where(
        pos, t * (jnp.log(jnp.where(pos, t, 1.0)) - sc), 0.0),
        axis=1, keepdims=True)
    z_ref[0] += jnp.sum(t, axis=1, keepdims=True)
    scm = jnp.where(keep, sc, _NEG)
    m_prev = m_ref[0]
    m_new = jnp.maximum(m_prev, jnp.max(scm, axis=1, keepdims=True))
    l_ref[0] = l_ref[0] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.where(keep, jnp.exp(scm - m_new), 0.0), axis=1,
        keepdims=True)
    m_ref[0] = m_new


def indexer_loss(q, k, lse, mask, scores, scale, block_q=512, block_k=512):
    """(the indexer's loss, (z, lse_i) per row, each [b, s, 1], for the
    backward pass): the mean over the queries of KL(target || softmax of
    the scores over the kept keys), the target being the heads' summed
    probabilities of the main attention over the kept keys, normalised.
    With the target's row sum z and the scores' row statistic lse_i, a
    row's term is sum(t (log t - score)) / z - log z + lse_i."""
    b, h, s, d = q.shape
    hk = k.shape[1]
    bq, bk = _block(s, block_q), _block(s, block_k)
    # the causal tiles by query tile, each with all heads
    steps = _causal_steps(s, bq, bk, by_key=False)
    grid = (b, len(steps) // _FIELDS)
    _count_steps(math.prod(grid))
    tile_spec = pl.BlockSpec((1, bq, bk), lambda bi, t, st:
                             (bi, _field(st, t, _QT), _field(st, t, _KT)))
    row_spec = pl.BlockSpec((1, bq, 1), lambda bi, t, st:
                            (bi, _field(st, t, _QT), 0))
    a, z, m, l = pl.pallas_call(
        functools.partial(_loss_kernel, scale=scale, heads=h, group=h // hk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, h, bq, d), lambda bi, t, st:
                             (bi, 0, _field(st, t, _QT), 0)),
                pl.BlockSpec((1, hk, bk, d), lambda bi, t, st:
                             (bi, 0, _field(st, t, _KT), 0)),
                pl.BlockSpec((1, bq, h), lambda bi, t, st:
                             (bi, _field(st, t, _QT), 0)),
                tile_spec, tile_spec],
            out_specs=[row_spec] * 4,
            scratch_shapes=[pltpu.VMEM((bq, bk), F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, s, 1), F32)] * 4,
        # at the Keye widths: q of all 32 heads (4 MiB), buffered twice,
        # 8 MiB of the 23 MiB asked; eight float32 tiles and ``tgt`` 9 MiB
        compiler_params=_compiler_params(
            ("parallel", "arbitrary"),
            [((h, bq, d), q.dtype), ((hk, bk, d), k.dtype), ((bq, h), F32),
             ((bq, bk), mask.dtype), ((bq, bk), F32)]
            + [((bq, 1), F32)] * 4,
            _padded_bytes((bq, bk), F32), (bq, bk)),
        interpret=_interpret(),
        name="indexer_loss_rows",
    )(jnp.asarray(steps), q, k, _rows(lse), mask, scores)
    lse_i = m + jnp.log(l)
    return jnp.mean(a / z - jnp.log(z) + lse_i), (z, lse_i)


def sparse_attention_reference(q, k, v, mask, scale):
    """(out, probabilities [b, h, s, s]) in float32 ``jax.numpy``."""
    g = q.shape[1] // k.shape[1]
    kf = jnp.repeat(k.astype(F32), g, axis=1)
    vf = jnp.repeat(v.astype(F32), g, axis=1)
    s = jnp.einsum("bhqd,bhsd->bhqs", q.astype(F32), kf,
                   precision="highest") * scale
    keep = (mask > 0)[:, None]
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    p = jnp.where(keep, p, 0.0)
    return jnp.einsum("bhqs,bhsd->bhqd", p, vf, precision="highest"), p


# ---------------------------------------------------------------------------
# the indexer's loss and the differentiable whole
# ---------------------------------------------------------------------------

def _indexer_kl(scores, target, mask):
    """The indexer's loss from whole arrays (``indexer_loss`` is the
    kernels' form): the mean over the queries of KL(target, normalised ||
    softmax of the scores over the kept keys)."""
    keep = mask > 0
    target = target / jnp.sum(target, -1, keepdims=True)
    logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
    live = keep & (target > 0)
    kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                   - jnp.where(live, logq, 0.0)), 0.0)
    return jnp.mean(jnp.sum(kl, -1))


def _forward(q, k, v, qi, ki, w, topk, scale):
    with jax.named_scope("indexer"):
        scores = indexer_scores(qi, ki, w)
    with jax.named_scope("select"):
        mask = topk_select(scores, topk)
    with jax.named_scope("sparse_attn"):
        out, lse = sparse_attn_fwd(q, k, v, mask, scale)
    with jax.named_scope("indexer"):
        loss, rows = indexer_loss(q, k, lse, mask, scores, scale)
    return out, loss, lse, mask, rows


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def learned_sparse_attention(q, k, v, qi, ki, w, topk, scale):
    """(out [b, h, s, d], the indexer's loss) with the Pallas kernels."""
    out, loss, _, _, _ = _forward(q, k, v, qi, ki, w, topk, scale)
    return out, loss


def _lsa_fwd(q, k, v, qi, ki, w, topk, scale):
    out, loss, lse, mask, (z, lse_i) = _forward(q, k, v, qi, ki, w, topk,
                                                scale)
    # the kernels' [.., s, 1] statistics pad their last dimension to a
    # lane tile in HBM: keep [.., s]
    return (out, loss), (q, k, v, qi, ki, w, out, lse[..., 0], mask,
                         z[..., 0], lse_i[..., 0])


def _lsa_bwd(topk, scale, res, cts):
    d_out, d_loss = cts
    telemetry.default_tracer().metrics.inc("attn.sparse.target_in_backward")
    # the scores are made again from the same operands as in the forward
    # pass: behind a barrier, or XLA merges the two calls and keeps the
    # forward's float32 [seq, seq] array alive
    q, k, v, qi, ki, w, out, lse, mask, z, lse_i, d_out = \
        jax.lax.optimization_barrier((*res, d_out))
    with jax.named_scope("indexer"):
        scores = indexer_scores(qi, ki, w)
    with jax.named_scope("sparse_attn"):
        dq, dk, dv, d_scores = sparse_attn_bwd(
            q, k, v, out, lse[..., None], d_out.astype(q.dtype), mask,
            scores, (z[..., None], lse_i[..., None]), scale)
    with jax.named_scope("indexer"):
        coef = d_loss / (scores.shape[0] * scores.shape[1])
        dqi, dki, dw = (g * coef.astype(g.dtype) for g in
                        _indexer_scores_bwd(qi, ki, w, d_scores))
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), dqi, dki, dw)


learned_sparse_attention.defvjp(_lsa_fwd, _lsa_bwd)


def learned_sparse_attention_reference(q, k, v, qi, ki, w, topk, scale):
    """The whole function's twin for the tests, in plain ``jax.numpy`` and
    differentiated by jax: dense [b, h, s, s] probabilities, so for small
    sizes only. The target and what the indexer reads are under
    ``stop_gradient`` as the kernels' rule has it."""
    scores = indexer_scores_reference(qi, ki, w)
    mask = topk_select_reference(jax.lax.stop_gradient(scores), topk)
    out, p = sparse_attention_reference(q, k, v, mask, scale)
    target = jax.lax.stop_gradient(jnp.mean(p, 1))
    loss = _indexer_kl(scores, target, mask)
    return out.astype(q.dtype), loss
