"""Mixture-of-Experts dispatch/combine on raw arrays (GShard algorithm).

Replaces the reference's MoE stack
(/root/reference/python/paddle/incubate/distributed/models/moe/
moe_layer.py:263 MoELayer, MoEScatter/MoEGather PyLayers, global_scatter/
global_gather comm ops): instead of index-based scatter over NCCL
all-to-all, the TPU-native form is the dense dispatch/combine einsum —
one-hot capacity-slotted routing whose expert dimension GSPMD shards over
the 'ep' mesh axis, lowering the dispatch to an ICI all-to-all
automatically.
"""
from __future__ import annotations

from typing import Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["topk_gating", "moe_dispatch_combine", "moe_mlp_forward",
           "moe_ragged_forward", "moe_share_forward", "route_softmax",
           "route_sigmoid"]


def topk_gating(logits, top_k: int, capacity: int):
    """GShard top-k gating with capacity slots.

    logits [T, E] → (dispatch [T, E, C] bool-ish f32,
                     combine  [T, E, C] f32 weights,
                     aux_loss scalar,
                     stats dict: tokens_per_expert [E] (routed within
                     capacity), assigned_per_expert [E] (pre-capacity),
                     dropped_fraction scalar — the capacity-overflow
                     diagnostics the reference MoE surfaces via
                     moe/grad_clip + utils counters)
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    gates_list = []
    masks = []
    remaining = probs
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = jax.nn.one_hot(idx, e, dtype=jnp.float32)
        gates_list.append((probs * mask).sum(-1))
        masks.append(mask)
        remaining = remaining * (1.0 - mask)

    # load-balancing aux loss (GShard eq. Switch-style): E * sum(me * ce)
    me = probs.mean(axis=0)                      # mean prob per expert
    ce = masks[0].mean(axis=0)                   # top-1 assignment fraction
    aux_loss = (me * ce).sum() * e

    # capacity assignment: position of each token within its expert queue
    dispatch = jnp.zeros((t, e, capacity), jnp.float32)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    # running per-expert fill across the k choices
    prior_fill = jnp.zeros((e,), jnp.float32)
    denom = sum(gates_list)
    denom = jnp.maximum(denom, 1e-9)
    for mask, gate in zip(masks, gates_list):
        pos = jnp.cumsum(mask, axis=0) - mask + prior_fill[None, :]  # [T,E]
        in_cap = (pos < capacity).astype(jnp.float32) * mask
        pos_idx = jnp.minimum(pos, capacity - 1).astype(jnp.int32)
        slot = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)  # [T,E,C]
        d = in_cap[..., None] * slot
        dispatch = dispatch + d
        combine = combine + d * (gate / denom)[:, None, None]
        prior_fill = prior_fill + mask.sum(axis=0)

    assigned = sum(m.sum(axis=0) for m in masks)       # [E] pre-capacity
    routed = dispatch.sum(axis=(0, 2))                 # [E] within capacity
    dropped = 1.0 - routed.sum() / jnp.maximum(assigned.sum(), 1.0)
    stats = {"tokens_per_expert": routed,
             "assigned_per_expert": assigned,
             "dropped_fraction": dropped}
    return dispatch, combine, aux_loss, stats


def moe_dispatch_combine(x, gate_w, w1, w2, top_k: int,
                         capacity_factor: float, activation=jax.nn.gelu,
                         ep_sharding=None):
    """Full MoE FFN: x [B, S, D] → (out [B, S, D], aux_loss, stats).

    w1 [E, D, H], w2 [E, H, D]. When ep_sharding (a NamedSharding for the
    [E, C, D] expert-batch layout) is given, the dispatched tensor gets a
    sharding constraint so GSPMD all-to-alls tokens to expert shards.
    stats: see topk_gating (expert utilization + token-drop counters).
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    e = w1.shape[0]
    t = tokens.shape[0]
    capacity = max(1, int(capacity_factor * top_k * t / e))
    # round capacity to a lane-friendly multiple
    capacity = -(-capacity // 8) * 8

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    dispatch, combine, aux, stats = topk_gating(logits, top_k, capacity)
    stats = dict(stats, capacity=jnp.float32(capacity))

    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), tokens)
    if ep_sharding is not None:
        expert_in = jax.lax.with_sharding_constraint(expert_in, ep_sharding)
    h = activation(jnp.einsum("ecd,edh->ech", expert_in, w1.astype(x.dtype)))
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2.astype(x.dtype))
    if ep_sharding is not None:
        expert_out = jax.lax.with_sharding_constraint(expert_out, ep_sharding)
    out = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), expert_out)
    return out.reshape(b, s, d), aux, stats


def _grouped_mm(lhs, rhs, group_sizes):
    """Grouped matmul over contiguous per-expert row segments:
    lhs [R, K] x rhs [E, K, N] -> [R, N], rows partitioned into E
    segments by group_sizes.

    XLA's own lowering of lax.ragged_dot, not the Pallas megablox gmm
    kernel. No cell of the benchmark runs this function; the same
    lowering under ``moe_share_forward`` is the Keye cell's
    ``ragged-dot-none`` (PERF.md section 5), where the expert layer's
    time lies in dispatch and combine more than in the matmuls.
    """
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def moe_ragged_forward(x, gate_w, w1, w2, top_k: int,
                       activation=jax.nn.gelu, capacity_factor=None):
    """Sort-based DROPLESS MoE FFN (the large-E path):
    x [B, S, D] → (out [B, S, D], aux_loss, stats).

    The dense GShard dispatch materializes [T, E, C] one-hot tensors —
    fine at E=4, ruinous at DeepSeek-scale E (the dispatch tensor dwarfs
    the activations). Here token→expert assignments are SORTED by
    expert id (a [T*k] argsort, static shape) and the expert FFNs run
    as grouped matmuls via jax.lax.ragged_dot over the contiguous
    per-expert segments — memory is O(T*k*D) regardless of E, and no
    token is ever dropped (no capacity), so dropped_fraction ≡ 0.

    Reference analog: the index-based MoEScatter/MoEGather path
    (/root/reference/python/paddle/incubate/distributed/models/moe/
    moe_layer.py:263) — the reference also routes by index, over NCCL;
    this is its on-chip form. For expert-parallel GSPMD sharding use
    the dense path (moe_dispatch_combine): ragged segment sizes are
    data-dependent, which GSPMD cannot shard over an 'ep' axis.
    capacity_factor is accepted for signature parity and ignored
    (dropless has no capacity).
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    t = tokens.shape[0]
    e = w1.shape[0]

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    top_p, top_i = jax.lax.top_k(probs, top_k)                 # [T, k]
    gates = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux loss: same Switch-style formula as the dense path (top-1 mask)
    ce = jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32).mean(axis=0)
    aux_loss = (probs.mean(axis=0) * ce).sum() * e

    flat_expert = top_i.reshape(t * top_k)                     # [T*k]
    # flat layout is token-major (flat slot i = token i//k, choice i%k),
    # so the token index needs no stored array — int32 metadata only
    order = jnp.argsort(flat_expert, stable=True).astype(jnp.int32)
    sorted_tok = order // top_k
    xs = jnp.take(tokens, sorted_tok, axis=0)                  # [T*k, D]
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)

    h = activation(_grouped_mm(xs, w1.astype(xs.dtype), group_sizes))
    ys = _grouped_mm(h, w2.astype(xs.dtype), group_sizes)
    # combine: weighted scatter-ADD back to token rows. XLA fuses the
    # multiply into the scatter and transposes it to a gather; a
    # scatter-free rewrite (bijective-inverse Pallas permute + reshape
    # reduce, custom vjps) breaks that fusion at its custom_vjp
    # boundaries. No cell of the benchmark runs this function: not
    # measured on the chip.
    wsorted = gates.reshape(t * top_k)[order].astype(ys.dtype)
    out = jnp.zeros((t, d), ys.dtype).at[sorted_tok].add(
        ys * wsorted[:, None])

    stats = {"tokens_per_expert": group_sizes.astype(jnp.float32),
             "assigned_per_expert": group_sizes.astype(jnp.float32),
             "dropped_fraction": jnp.float32(0.0)}
    return out.reshape(b, s, d).astype(x.dtype), aux_loss, stats


def route_softmax(logits, top_k: int, norm_topk_prob: bool = True,
                  scaling: float = 1.0):
    """Keye-VL-2.0's (Qwen3-MoE's) routing rule: softmax over ALL the
    float32 ``logits`` [T, E], the ``top_k`` largest, their weights
    divided by their sum when ``norm_topk_prob``, times ``scaling``
    (Laguna's ``moe_routed_scaling_factor``; at 1.0 no multiply is
    traced). -> (top_i [T, k], gates [T, k])."""
    probs = jax.nn.softmax(logits, axis=-1)                    # [T, E]
    top_p, top_i = jax.lax.top_k(probs, top_k)                 # [T, k]
    gates = top_p / jnp.sum(top_p, -1, keepdims=True) \
        if norm_topk_prob else top_p
    if scaling != 1.0:
        gates = gates * scaling
    return top_i, gates


def route_sigmoid(logits, top_k: int, norm_topk_prob: bool = True,
                  expert_bias=None, scaling: float = 1.0):
    """LFM2-MoE's (DeepSeek-V3's) routing rule: ``s = sigmoid(logits)``
    in float32; the ``top_k`` largest of ``s + expert_bias`` are CHOSEN
    (the bias [E] steers the selection and takes no gradient; ``None`` is
    no bias); the weights are ``s`` at the chosen experts, divided by
    their sum + 1e-6 when ``norm_topk_prob``, times ``scaling``. ->
    (top_i [T, k], gates [T, k])."""
    s = jax.nn.sigmoid(logits)                                 # [T, E]
    pick = s if expert_bias is None else \
        s + jax.lax.stop_gradient(expert_bias.astype(s.dtype))
    _, top_i = jax.lax.top_k(pick, top_k)                      # [T, k]
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if norm_topk_prob:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6)
    return top_i, top_s * scaling


# the gate's activation of a gated expert: w_down(act(w_gate x) * w_up x)
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def moe_share_forward(x, gate_w, w_gate, w_up, w_down, top_k: int,
                      first_expert: int = 0, norm_topk_prob: bool = True,
                      route=route_softmax, activation: str = "silu",
                      router_input=None):
    """One share's part of a gated-expert layer: x [B, S, D] ->
    (out [B, S, D], rows [E_held] int32, walked int32).

    ``gate_w`` [D, E] gives every token its float32 logits over ALL E
    experts, from ``x`` or, where it is given, from ``router_input``
    [B, S, D] (a router that reads another tensor than its experts do,
    and takes its gradient there);
    ``route(logits, top_k, norm_topk_prob)`` turns them into the
    token's ``top_k`` experts and their weights (``route_softmax``, or
    ``route_sigmoid`` with its bias and scaling bound by
    ``functools.partial``); ``w_gate`` / ``w_up`` [E_held, D, H] and
    ``w_down`` [E_held, H, D] are the experts ``first_expert ..
    first_expert + E_held - 1`` that live here, each computing
    ``w_down(act(w_gate x) * w_up x)``, ``act`` the static ``activation``
    (``silu`` or ``relu``). ``out`` is the sum over a
    token's chosen experts THAT ARE HELD of weight * expert(x): what the
    other shares hold is theirs to add (expert parallelism's exchange,
    or nothing on a single share). ``rows`` counts the rows each held
    expert computed, ``walked`` the rows the layer's row movement went
    over to reach them (whole blocks: at least ``sum(rows)``).

    Dropless at static shapes: the token-to-expert assignments are
    sorted with the held experts first, and the sorted rows are walked
    in chunks of twice an even routing's share (rounded up to 16 rows: at
    1.25 times the share a layer's held rows crossed the chunk's end from
    step to step and a step's time with them). A chunk that holds no held
    row is skipped by ``lax.cond``, so every row up to the worst case
    (all T * top_k) has its chunk while an even routing runs one, half
    full by design. The chunk is what bounds the memory and what the
    weight gradients' float32 accumulators are paid once for, so it stays
    that large; inside it the work follows the rows that are there: the
    three ``lax.ragged_dot`` by their group sizes, and the gather of the
    tokens' rows and the scatter-add of the products by walking the chunk
    in blocks of ``_ROW_BLOCK`` rows only as far as the held rows reach
    (``_take_rows``, ``_add_rows``). Each chunk's products are made again
    in the backward pass (``_share_experts``): nothing of size rows x
    width is kept.
    """
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    t = tokens.shape[0]
    e, n_held = gate_w.shape[1], w_gate.shape[0]
    n_rows = t * top_k

    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r}; there are: "
                         f"{', '.join(ACTIVATIONS)}")
    # the router is a step of its own in the trace, beside the experts it
    # is computed with
    routed_on = tokens if router_input is None \
        else router_input.reshape(t, d)
    with jax.named_scope("router"):
        logits = routed_on.astype(jnp.float32) @ gate_w.astype(jnp.float32)
        top_i, gates = route(logits, top_k, norm_topk_prob)

    local = top_i.reshape(n_rows) - first_expert
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    # slot i of the flat layout is token i // k, choice i % k
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)

    even = n_rows * n_held / e
    row_chunk = min(n_rows, -(-int(2 * even) // 16) * 16)
    n_chunks = -(-n_rows // row_chunk)
    # rows past the last assignment never lie under a held expert
    order = jnp.pad(order, (0, n_chunks * row_chunk - n_rows),
                    constant_values=n_rows - 1)
    out = _share_experts(tokens, gates.reshape(n_rows), w_gate, w_up,
                         w_down, order, sizes, top_k, row_chunk, activation)
    _, lives, block = _chunks(order, sizes, row_chunk)
    walked = block * jnp.sum(_live_blocks(lives, block))
    return out.reshape(b, s, d).astype(x.dtype), sizes, walked


# Rows a step of the share's row movement gathers or scatter-adds. On the
# chip (one layer at [16384, 2048], a 32,768-row chunk, 16,500 rows held):
# PERF.md section 6, PR 35.
_ROW_BLOCK = 2048


def _live_blocks(live, block):
    """Blocks of ``block`` rows that hold one of the first ``live``."""
    return (live + block - 1) // block


def _take_rows(src, tok, live, block):
    """``src[tok]`` [R, D] with the rows from ``live`` on zero. Starts
    from zeros and gathers ``_live_blocks`` blocks of ``block`` rows: the
    rows past the last live block are never touched. ``_add_rows`` is its
    transpose."""
    r = tok.shape[0]

    def body(i, out):
        # where block does not divide R the last block moves back over
        # rows the one before it wrote, and writes them the same
        lo = jnp.minimum(i * block, r - block)
        at = jax.lax.dynamic_slice(tok, (lo,), (block,))
        keep = (lo + jnp.arange(block) < live)[:, None]
        got = jnp.where(keep, jnp.take(src, at, axis=0),
                        jnp.zeros((), src.dtype))
        return jax.lax.dynamic_update_slice(out, got, (lo, 0))

    return jax.lax.fori_loop(0, _live_blocks(live, block), body,
                             jnp.zeros((r, src.shape[1]), src.dtype))


def _add_rows(dst, tok, rows, live, block):
    """``dst`` [T, D] with ``rows[i]`` added at ``tok[i]`` for i < live,
    ``_live_blocks`` blocks of ``block`` rows at a time into ``dst``
    itself: what ``rows`` holds from ``live`` on is never read."""
    r, d = rows.shape

    def body(i, dst):
        lo = jnp.minimum(i * block, r - block)
        at = jax.lax.dynamic_slice(tok, (lo,), (block,))
        row = lo + jnp.arange(block)
        keep = ((row >= i * block) & (row < live))[:, None]
        add = jnp.where(keep, jax.lax.dynamic_slice(rows, (lo, 0), (block, d)),
                        jnp.zeros((), rows.dtype))
        return dst.at[at].add(add.astype(dst.dtype))

    return jax.lax.fori_loop(0, _live_blocks(live, block), body, dst)


def _chunk_rows(order, sizes, lo, top_k, row_chunk):
    """Of the sorted rows lo .. lo + row_chunk: (their slots, their
    tokens, the rows of each held expert among them)."""
    idx = jax.lax.dynamic_slice(order, (lo,), (row_chunk,))
    ends = jnp.cumsum(sizes)
    gs = jnp.clip(ends, lo, lo + row_chunk) \
        - jnp.clip(ends - sizes, lo, lo + row_chunk)
    return idx, idx // top_k, gs


def _chunk_products(xs, flat_gates, w_gate, w_up, w_down, idx, gs, live,
                    activation="silu"):
    """The held experts' weighted outputs for a chunk's gathered rows
    ``xs`` [row_chunk, D], row by row."""
    cdt = xs.dtype
    # a row past the chunk's held rows lies under no expert: what
    # ragged_dot leaves there is not defined on every backend (the TPU's
    # leaves what the buffer held), so such rows are cut out of every
    # product, forward and (where's vjp) backward
    held = (jnp.arange(xs.shape[0]) < live)[:, None]
    dot = lambda a, w: jnp.where(
        held, jax.lax.ragged_dot(a, w.astype(cdt), gs), jnp.zeros((), cdt))
    h = ACTIVATIONS[activation](dot(xs, w_gate)) * dot(xs, w_up)
    ys = dot(h, w_down)
    return ys * jnp.take(flat_gates, idx).astype(ys.dtype)[:, None]


def _chunks(order, sizes, row_chunk):
    """(chunk starts, each chunk's live rows: those under a held expert,
    which come first, the rows of a block of the row movement)."""
    n = order.shape[0] // row_chunk
    starts = jnp.arange(n, dtype=jnp.int32) * row_chunk
    return (starts, jnp.clip(jnp.sum(sizes) - starts, 0, row_chunk),
            min(_ROW_BLOCK, row_chunk))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _share_experts(tokens, flat_gates, w_gate, w_up, w_down, order, sizes,
                   top_k, row_chunk, activation="silu"):
    """The held experts' weighted outputs summed per token, a chunk of
    sorted rows at a time; a chunk past the held rows is skipped, and a
    chunk that runs gathers its tokens' rows and adds its products into
    the carried sum block by block up to its last held row. Its own vjp
    walks the chunks again, each differentiated where it stands, so no
    chunk's operands are kept for the backward pass."""
    starts, lives, block = _chunks(order, sizes, row_chunk)

    def run(out, lo, live):
        idx, tok, gs = _chunk_rows(order, sizes, lo, top_k, row_chunk)
        xs = _take_rows(tokens, tok, live, block)
        return _add_rows(out, tok, _chunk_products(
            xs, flat_gates, w_gate, w_up, w_down, idx, gs, live, activation),
            live, block)

    def step(out, chunk):
        return jax.lax.cond(chunk[1] > 0, run, lambda o, *_: o,
                            out, *chunk), None

    out, _ = jax.lax.scan(step, jnp.zeros(tokens.shape, tokens.dtype),
                          (starts, lives))
    return out


def _share_experts_fwd(tokens, flat_gates, w_gate, w_up, w_down, order,
                       sizes, top_k, row_chunk, activation="silu"):
    out = _share_experts(tokens, flat_gates, w_gate, w_up, w_down, order,
                         sizes, top_k, row_chunk, activation)
    return out, (tokens, flat_gates, w_gate, w_up, w_down, order, sizes)


def _share_experts_bwd(top_k, row_chunk, activation, res, d_out):
    tokens, flat_gates, w_gate, w_up, w_down, order, sizes = res
    starts, lives, block = _chunks(order, sizes, row_chunk)
    diff = (flat_gates, w_gate, w_up, w_down)

    def run(acc, lo, live):
        # the gather's transpose is the scatter-add and the other way
        # round: the products' cotangent is d_out's rows taken, and the
        # tokens' is d xs added into their accumulator
        idx, tok, gs = _chunk_rows(order, sizes, lo, top_k, row_chunk)
        _, vjp = jax.vjp(
            lambda xs, *a: _chunk_products(xs, *a, idx, gs, live,
                                           activation),
            _take_rows(tokens, tok, live, block), *diff)
        d_xs, *grads = vjp(_take_rows(d_out, tok, live, block))
        return (_add_rows(acc[0], tok, d_xs, live, block),
                *(a + g.astype(a.dtype) for a, g in zip(acc[1:], grads)))

    def step(acc, chunk):
        return jax.lax.cond(chunk[1] > 0, run, lambda a, *_: a,
                            acc, *chunk), None

    acc0 = (jnp.zeros(tokens.shape, tokens.dtype),
            *(jnp.zeros(a.shape, jnp.float32) for a in diff))
    acc, _ = jax.lax.scan(step, acc0, (starts, lives))
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (acc[0], *(g.astype(a.dtype) for g, a in zip(acc[1:], diff)),
            zero(order), zero(sizes))


_share_experts.defvjp(_share_experts_fwd, _share_experts_bwd)


moe_mlp_forward = moe_dispatch_combine
