"""Ring attention: blockwise causal attention with the sequence sharded
over a mesh axis, KV blocks rotated around the ring via ppermute.

Fills the reference's long-context gap (SURVEY.md §5.7: Paddle has only
Megatron-SP and an early segment-parallel mode — no ring attention). This
is the TPU-native design: the ring rides ICI neighbor links, compute on
the current KV block overlaps the DMA of the next one (XLA schedules the
ppermute async), and the online-softmax merge makes the math exact.

The inner block is the Pallas flash kernel (ops/pallas/flash_attention):
each ring step computes (out_blk, lse_blk) with blocked online softmax —
no [s_q, s_kv] score materialization — and merges via
logaddexp(lse, lse_blk). The backward is a second ring pass: q/out/do/lse
stay resident while (k, v, dk, dv) circulate; each step runs the flash
backward kernels against the MERGED lse, so dk/dv accumulate exactly and
arrive home after n hops. GQA needs no head expansion on the Pallas path
(kv-head index mapping + grouped dk/dv accumulation live in the kernel).

A jnp blockwise fallback (still per-shard-block, f32) serves CPU tests
and shapes the kernel doesn't tile.

Used inside shard_map / jitted programs; also exposed as an eager Tensor
op through paddle_tpu.nn.functional.ring_attention.
"""
from __future__ import annotations

import functools
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30

__all__ = ["ring_attention_local", "ring_attention", "zigzag_indices",
           "inverse_zigzag_indices"]


# ---------------------------------------------------------------------------
# zigzag sequence placement (causal load balancing)
#
# Contiguous placement wastes ~half the causal compute: rank r holds
# chunk r, and every ring step where the visiting KV chunk is later than
# r is fully masked (ring_attention computed it then zeroed it).
# Zigzag placement splits the sequence into 2n blocks and
# gives rank r the PAIR (block r, block 2n-1-r): at every ring step
# exactly half of the 2x2 (q-half x kv-half) block pairs are visible —
#   kv from an earlier rank: full q attends its early-kv half;
#   kv from a later rank:   the late q half attends both kv halves;
#   own kv (t=0):           both diagonals + late-q x early-kv.
# so causal work is balanced across ranks and no block is computed just
# to be masked. (Same trick as llama3-style zigzag / striped attention.)
# ---------------------------------------------------------------------------

def zigzag_indices(seq_len: int, n: int):
    """Global seq index order such that a contiguous n-way shard of the
    reordered sequence gives rank r the zigzag pair (block r, 2n-1-r)."""
    import numpy as np
    if seq_len % (2 * n):
        raise ValueError(f"zigzag needs seq_len ({seq_len}) divisible "
                         f"by 2*n ({2 * n})")
    blk = seq_len // (2 * n)
    order = []
    for r in range(n):
        order.extend(range(r * blk, (r + 1) * blk))
        order.extend(range((2 * n - 1 - r) * blk, (2 * n - r) * blk))
    return np.asarray(order, np.int32)


def inverse_zigzag_indices(seq_len: int, n: int):
    import numpy as np
    order = zigzag_indices(seq_len, n)
    inv = np.empty_like(order)
    inv[order] = np.arange(seq_len, dtype=np.int32)
    return inv


# ---------------------------------------------------------------------------
# per-block fwd/bwd implementations (pallas | jnp), shared signature:
#   blk_fwd(q, k, v, causal, scale)            -> out [b,s,h,d], lse [b,h,s]
#   blk_bwd(q, k, v, out, lse, do, causal, scale) -> dq, dk, dv  (f32)
# ---------------------------------------------------------------------------

def _jnp_blk_fwd(q, k, v, causal, scale):
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    if hk != h:
        k = jnp.repeat(k, h // hk, axis=2)
        v = jnp.repeat(v, h // hk, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-30)[..., None].swapaxes(1, 2)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.astype(q.dtype), lse


def _jnp_blk_bwd(q, k, v, out, lse, do, causal, scale):
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    group = h // hk
    ke, ve = k, v
    if group > 1:
        ke = jnp.repeat(k, group, axis=2)
        ve = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, ke).astype(jnp.float32) * scale
    if causal:
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jnp.where(s > _NEG_INF * 0.5, jnp.exp(s - lse[..., None]), 0.0)
    do32 = do.astype(jnp.float32)
    delta = jnp.sum(out.astype(jnp.float32) * do32, axis=-1)  # [b,s,h]
    delta = delta.swapaxes(1, 2)                              # [b,h,s]
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, do32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do32, ve.astype(jnp.float32))
    ds = p * (dp - delta[..., None]) * scale
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, ke.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
    if group > 1:
        dk = dk.reshape(b, sk, hk, group, d).sum(axis=3)
        dv = dv.reshape(b, sk, hk, group, d).sum(axis=3)
    return dq, dk, dv


def _interp_vma_fallback(q) -> bool:
    """Pallas interpret mode (the CPU test vehicle) cannot evaluate
    kernels whose operands carry varying-manual-axes tags (its internal
    dynamic_slices trip the vma checker); use the jnp oracle there.
    Real TPU lowering takes the tagged out_shape fine."""
    from ..ops.pallas.flash_attention import _interpret
    vma = getattr(getattr(q, "aval", None), "vma", None)
    return bool(vma) and _interpret()


def _pallas_blk_fwd(q, k, v, causal, scale):
    if _interp_vma_fallback(q):
        return _jnp_blk_fwd(q, k, v, causal, scale)
    from ..ops.pallas.flash_attention import flash_attention_with_lse
    from ..ops.flash_attention import pallas_attention_plan
    plan = pallas_attention_plan(q, k, min_seq=128) or (None, None)
    return flash_attention_with_lse(q, k, v, causal=causal, scale=scale,
                                    block_q=plan[0] or q.shape[1],
                                    block_k=plan[1] or k.shape[1])


def _pallas_blk_bwd(q, k, v, out, lse, do, causal, scale):
    if _interp_vma_fallback(q):
        return _jnp_blk_bwd(q, k, v, out, lse, do, causal, scale)
    from ..ops.pallas.flash_attention import flash_attention_bwd_block
    from ..ops.flash_attention import pallas_attention_plan
    plan = pallas_attention_plan(q, k, min_seq=128) or (None, None)
    return flash_attention_bwd_block(q, k, v, out, lse, do, causal=causal,
                                     scale=scale,
                                     block_q=plan[0] or q.shape[1],
                                     block_k=plan[1] or k.shape[1])


def _pallas_ok(q_shape, k_shape, halved=False):
    # shared gate with ops.flash_attention (ring shards are often shorter
    # than a full sequence, hence the lower min_seq). Shape-only on
    # purpose: the eligibility decision is Python-static under tracing,
    # so the gate takes shapes, not arrays — the decision provably
    # cannot depend on traced VALUES (and flightcheck's taint pass can
    # see that). halved=True gates the zigzag path, which feeds the
    # kernel half-blocks.
    import jax
    from ..ops.flash_attention import pallas_attention_plan
    qs, ks = list(q_shape), list(k_shape)
    if halved:
        qs[1] //= 2
        ks[1] //= 2
    return pallas_attention_plan(
        jax.ShapeDtypeStruct(tuple(qs), jnp.float32),
        jax.ShapeDtypeStruct(tuple(ks), jnp.float32),
        min_seq=128) is not None


# ---------------------------------------------------------------------------
# zigzag per-step block attention: local q = [early half | late half],
# visiting kv likewise. rel = sign(src - my): -1 earlier, 0 self, +1
# later. Every branch computes exactly the visible half of the work.
# ---------------------------------------------------------------------------

def _merge_pair(o1, l1, o2, l2):
    """Online-softmax merge of two partial results (f32)."""
    l = jnp.logaddexp(l1, l2)
    c1 = jnp.exp(l1 - l).swapaxes(1, 2)[..., None]
    c2 = jnp.exp(l2 - l).swapaxes(1, 2)[..., None]
    return o1.astype(jnp.float32) * c1 + o2.astype(jnp.float32) * c2, l


def _zz_step_fwd(blk_fwd, q, k_cur, v_cur, rel, scale):
    """One zigzag ring step forward → (out f32 [b,s,h,d], lse [b,h,s]);
    invisible q positions carry lse=-inf / out=0 (merge no-ops)."""
    b, s, h, d = q.shape
    half = s // 2
    q_e, q_l = q[:, :half], q[:, half:]
    k_e, k_l = k_cur[:, :half], k_cur[:, half:]
    v_e, v_l = v_cur[:, :half], v_cur[:, half:]
    z_o = jnp.zeros((b, half, h, d), jnp.float32)
    z_l = jnp.full((b, h, half), _NEG_INF, jnp.float32)

    def earlier(_):
        # full q attends the visiting EARLY kv half only
        o, l = blk_fwd(q, k_e, v_e, False, scale)
        return o.astype(jnp.float32), l

    def later(_):
        # only the late q half attends (both kv halves, fully visible)
        o, l = blk_fwd(q_l, k_cur, v_cur, False, scale)
        return (jnp.concatenate([z_o, o.astype(jnp.float32)], axis=1),
                jnp.concatenate([z_l, l], axis=2))

    def diag(_):
        o_e, l_e = blk_fwd(q_e, k_e, v_e, True, scale)
        o_l1, l_l1 = blk_fwd(q_l, k_e, v_e, False, scale)
        o_l2, l_l2 = blk_fwd(q_l, k_l, v_l, True, scale)
        o_l, l_l = _merge_pair(o_l1, l_l1, o_l2, l_l2)
        return (jnp.concatenate([o_e.astype(jnp.float32), o_l], axis=1),
                jnp.concatenate([l_e, l_l], axis=2))

    return jax.lax.switch(rel + 1, [earlier, diag, later], None)


def _zz_step_bwd(blk_bwd, q, k_cur, v_cur, out, lse, do, rel, scale):
    """One zigzag ring step backward → (dq, dk, dv) f32, full shapes.
    out/lse are the MERGED forward results (exactness of per-block
    backward against merged lse — same invariant as the plain ring)."""
    b, s, h, d = q.shape
    half = s // 2
    kvh = k_cur.shape[2]
    q_e, q_l = q[:, :half], q[:, half:]
    k_e, k_l = k_cur[:, :half], k_cur[:, half:]
    v_e, v_l = v_cur[:, :half], v_cur[:, half:]
    o_e, o_l = out[:, :half], out[:, half:]
    do_e, do_l = do[:, :half], do[:, half:]
    lse_e, lse_l = lse[:, :, :half], lse[:, :, half:]
    zq = jnp.zeros((b, half, h, d), jnp.float32)
    zkv = jnp.zeros((b, half, kvh, d), jnp.float32)

    def earlier(_):
        dq, dk_e, dv_e = blk_bwd(q, k_e, v_e, out, lse, do, False, scale)
        return (dq.astype(jnp.float32),
                jnp.concatenate([dk_e.astype(jnp.float32), zkv], axis=1),
                jnp.concatenate([dv_e.astype(jnp.float32), zkv], axis=1))

    def later(_):
        dq_l, dk, dv = blk_bwd(q_l, k_cur, v_cur, o_l, lse_l, do_l,
                               False, scale)
        return (jnp.concatenate([zq, dq_l.astype(jnp.float32)], axis=1),
                dk.astype(jnp.float32), dv.astype(jnp.float32))

    def diag(_):
        dq_e, dk1, dv1 = blk_bwd(q_e, k_e, v_e, o_e, lse_e, do_e, True,
                                 scale)
        dq_l1, dk2, dv2 = blk_bwd(q_l, k_e, v_e, o_l, lse_l, do_l,
                                  False, scale)
        dq_l2, dk3, dv3 = blk_bwd(q_l, k_l, v_l, o_l, lse_l, do_l, True,
                                  scale)
        dq = jnp.concatenate(
            [dq_e.astype(jnp.float32),
             dq_l1.astype(jnp.float32) + dq_l2.astype(jnp.float32)],
            axis=1)
        dk = jnp.concatenate(
            [dk1.astype(jnp.float32) + dk2.astype(jnp.float32),
             dk3.astype(jnp.float32)], axis=1)
        dv = jnp.concatenate(
            [dv1.astype(jnp.float32) + dv2.astype(jnp.float32),
             dv3.astype(jnp.float32)], axis=1)
        return dq, dk, dv

    return jax.lax.switch(rel + 1, [earlier, diag, later], None)


# ---------------------------------------------------------------------------
# the ring (custom_vjp: fwd merges lse online; bwd circulates dk/dv)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_attention_core(q, k, v, axis_name, causal, scale, use_pallas,
                         zigzag):
    out, _ = _ring_fwd(q, k, v, axis_name, causal, scale, use_pallas,
                       zigzag)
    return out


def _ring_fwd(q, k, v, axis_name, causal, scale, use_pallas, zigzag):
    blk_fwd = _pallas_blk_fwd if use_pallas else _jnp_blk_fwd
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, s, h, d = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        out, lse, k_cur, v_cur = carry
        src = jnp.mod(my - t, n)    # global chunk id we hold this step
        if causal and zigzag:
            rel = jnp.sign(src - my).astype(jnp.int32)
            o_blk, lse_blk = _zz_step_fwd(blk_fwd, q, k_cur, v_cur, rel,
                                          scale)
        elif causal:
            o_blk, lse_blk = jax.lax.cond(
                t == 0,
                lambda a: blk_fwd(a[0], a[1], a[2], True, scale),
                lambda a: blk_fwd(a[0], a[1], a[2], False, scale),
                (q, k_cur, v_cur))
            visible = jnp.logical_or(t == 0, src < my)
            lse_blk = jnp.where(visible, lse_blk, _NEG_INF)
            o_blk = jnp.where(visible, o_blk, 0.0)
        else:
            o_blk, lse_blk = blk_fwd(q, k_cur, v_cur, False, scale)
        out, lse_new = _merge_pair(out, lse, o_blk, lse_blk)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (out, lse_new, k_nxt, v_nxt), None

    # pcast to varying: zero-init carries are axis-invariant constants, but the scan
    # writes axis-varying values into them — required typing under the
    # (default) vma checker when shard_map is manual over a subset axis
    out0 = jax.lax.pcast(jnp.zeros((b, s, h, d), jnp.float32),
                         (axis_name,), to="varying")
    lse0 = jax.lax.pcast(jnp.full((b, h, s), _NEG_INF, jnp.float32),
                         (axis_name,), to="varying")
    (out, lse, _, _), _ = jax.lax.scan(
        step, (out0, lse0, k, v), jnp.arange(n))
    return out.astype(q.dtype), lse


def _ring_core_fwd(q, k, v, axis_name, causal, scale, use_pallas,
                   zigzag):
    out, lse = _ring_fwd(q, k, v, axis_name, causal, scale, use_pallas,
                         zigzag)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(axis_name, causal, scale, use_pallas, zigzag, res,
                   do):
    q, k, v, out, lse = res
    blk_bwd = _pallas_blk_bwd if use_pallas else _jnp_blk_bwd
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        src = jnp.mod(my - t, n)
        if causal and zigzag:
            rel = jnp.sign(src - my).astype(jnp.int32)
            dq_blk, dk_blk, dv_blk = _zz_step_bwd(
                blk_bwd, q, k_cur, v_cur, out, lse, do, rel, scale)
        elif causal:
            dq_blk, dk_blk, dv_blk = jax.lax.cond(
                t == 0,
                lambda a: blk_bwd(a[0], a[1], a[2], a[3], a[4], a[5],
                                  True, scale),
                lambda a: blk_bwd(a[0], a[1], a[2], a[3], a[4], a[5],
                                  False, scale),
                (q, k_cur, v_cur, out, lse, do))
            vis = jnp.logical_or(t == 0, src < my).astype(jnp.float32)
            dq_blk = dq_blk * vis
            dk_blk = dk_blk * vis
            dv_blk = dv_blk * vis
        else:
            dq_blk, dk_blk, dv_blk = blk_bwd(q, k_cur, v_cur, out, lse,
                                             do, False, scale)
        dq = dq + dq_blk.astype(jnp.float32)
        dk_cur = dk_cur + dk_blk.astype(jnp.float32)
        dv_cur = dv_cur + dv_blk.astype(jnp.float32)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (dq, k_nxt, v_nxt, dk_nxt, dv_nxt), None

    def zeros(x):
        return jax.lax.pcast(jnp.zeros(x.shape, jnp.float32),
                             (axis_name,), to="varying")

    dq0, dk0, dv0 = zeros(q), zeros(k), zeros(v)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step, (dq0, k, v, dk0, dv0), jnp.arange(n))
    # after n hops the dk/dv accumulators are back at their home shard
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_attention_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention_local(q, k, v, axis_name: str, causal: bool = True,
                         scale: Optional[float] = None,
                         use_pallas: Optional[bool] = None,
                         zigzag: bool = False):
    """Per-shard ring attention body (call inside shard_map).

    q/k/v: the LOCAL sequence chunk [b, s_local, h, d]. With
    zigzag=False the global sequence is the concatenation over
    `axis_name` in axis-index order; with zigzag=True (causal only) each
    rank holds the block PAIR (r, 2n-1-r) of the 2n-block split — see
    zigzag_indices — which halves the causal compute by balancing
    visible work across the ring. kv heads may be fewer than q heads
    (GQA). Differentiable (custom ring backward). Returns the local
    output chunk [b, s_local, h, d].
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if use_pallas is None:
        # zigzag computes on half-blocks — the kernel gate must pass for
        # the shapes actually fed to it
        use_pallas = _pallas_ok(q.shape, k.shape, halved=zigzag)
    if zigzag:
        if not causal:
            raise ValueError("zigzag placement only helps causal "
                             "attention; pass zigzag=False")
        if q.shape[1] % 2:
            raise ValueError("zigzag needs an even local sequence "
                             f"length, got {q.shape[1]}")
    return _ring_attention_core(q, k, v, axis_name, causal, scale,
                                bool(use_pallas), bool(zigzag))


def ring_attention(q, k, v, mesh, axis: str = "sep", causal: bool = True,
                   scale: Optional[float] = None,
                   use_pallas: Optional[bool] = None,
                   zigzag: Optional[bool] = None):
    """Whole-array entry: q/k/v [b, S_global, h, d] (sharded or not) →
    output with the sequence dim sharded over `axis`.

    zigzag (default: on for causal) load-balances causal work by
    computing in the zigzag sequence order internally — inputs/outputs
    keep the natural contiguous order; the permutation is applied and
    inverted inside."""
    jmesh = mesh.to_jax_mesh() if hasattr(mesh, "to_jax_mesh") else mesh
    n = jmesh.shape[axis]
    if zigzag is None:
        zigzag = bool(causal) and n > 1 and q.shape[1] % (2 * n) == 0
    spec = P(None, axis, None, None)
    # single-axis mesh: manual over everything, vma checker off (the
    # pre-CP behavior; pallas interpret mode dislikes vma tags).
    # multi-axis mesh: manual over `axis` ONLY so dp/mp compose as GSPMD
    # auto axes; the vma checker must stay ON there — jax 0.9
    # mis-validates out_specs when check_vma=False combines with a
    # subset axis_names (it demands the None entries "refer to" the
    # auto axes)
    from .fleet.pp_schedule import partial_manual_ok
    if set(jmesh.axis_names) == {axis} or not partial_manual_ok():
        # jax 0.4.x: partially-manual shard_map neither runs eagerly
        # (shard_map.py `if auto: raise NotImplementedError`) nor
        # lowers its collectives under jit (SPMD partitioner CHECK) —
        # run fully manual; the in/out specs only name `axis`, so other
        # mesh axes see replicated shards and numerics are unchanged
        sm_kwargs = dict(check_vma=False)
    else:
        sm_kwargs = dict(axis_names={axis})
    f = shard_map(
        partial(ring_attention_local, axis_name=axis, causal=causal,
                scale=scale, use_pallas=use_pallas, zigzag=zigzag),
        mesh=jmesh, in_specs=(spec, spec, spec), out_specs=spec,
        **sm_kwargs)
    if not zigzag:
        return f(q, k, v)
    # the permutation is a cross-shard all-to-all; re-pin the layouts so
    # the permuted operands and the final output keep the documented
    # seq-sharded placement instead of decaying to replicated
    ns = jax.sharding.NamedSharding(jmesh, spec)

    def pin(x):
        if isinstance(x, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(x, ns)
        return jax.device_put(x, ns)

    order = jnp.asarray(zigzag_indices(q.shape[1], n))
    inv = jnp.asarray(inverse_zigzag_indices(q.shape[1], n))
    out = f(pin(jnp.take(q, order, axis=1)),
            pin(jnp.take(k, order, axis=1)),
            pin(jnp.take(v, order, axis=1)))
    return pin(jnp.take(out, inv, axis=1))
