"""Paged-KV serving decode engine for Llama-family models.

Reference: the block_multihead_attention serving path
(/root/reference/python/paddle/incubate/nn/functional/
block_multihead_attention.py + paddle/phi/kernels/fusion/ CUDA kernels):
fixed-size KV pages + per-sequence block tables, so batched decode serves
mixed-length sequences without reallocation.

TPU-native structure: two compiled programs —
- prefill: dense causal attention over the prompt, k/v scattered into the
  page pool at precomputed flat slots;
- decode_step: one token for the whole batch; attention over the pool via
  ops.paged_attention.paged_attention_decode (Pallas scalar-prefetch
  kernel on TPU), pools donated so page writes are in-place in HBM.
The Python loop only replays decode_step with fresh host-side slot
mappings from the PagedKVCache block allocator.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..ops.paged_attention import (PagedKVCache, paged_attention_decode,
                                   ragged_paged_attention)
from ..ops.flash_attention import flash_attention
from ..ops.rms_norm import rms_norm
from ..ops.rope import build_rope_cache

__all__ = ["PagedLlamaDecoder"]


def _rotate_half(x):
    h1, h2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-h2, h1], axis=-1)


def _quantize_w(w):
    """Per-output-channel symmetric absmax int8 (the serving half of the
    quantization stack's PTQ weight scheme — same math as
    quantization.AbsmaxObserver over axis 0). Runs on-device (jnp) so a
    billion-parameter model quantizes without a host roundtrip.
    Returns (int8, scale[out])."""
    w = jnp.asarray(w, jnp.float32)
    scale = jnp.abs(w).max(axis=0) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    wi = jnp.clip(jnp.round(w / scale[None, :]), -127, 127).astype(jnp.int8)
    return wi, scale


def _quantize_w4(w):
    """Per-output-channel symmetric absmax int4, two values nibble-packed
    per int8 byte along the IN dim (rows 2i → low nibble, 2i+1 → high;
    same layout as nn.quant.weight_quantize int4 — see
    nn/quant/quantized_linear.py). Weight HBM reads drop 4× vs bf16.
    Returns (packed [in/2, out] int8, scale [out]) — _mm tells int4
    from int8 by the packed array having HALF the activation's in-dim
    (a string tag could not ride the weights pytree through jit).

    LAYOUT CONTRACT: this interleaved packing is for TP decoders and
    must be consumed with _mm(..., allow_kernel=False); the layout is
    not encoded in the (packed, scale) tuple, so pairing it with the
    default halves math silently computes garbage. Single-device
    decoders pack with _quantize_w4_halves."""
    w = jnp.asarray(w, jnp.float32)
    if w.shape[0] % 2:
        raise ValueError(f"int4 packing needs even in_features, "
                         f"got {w.shape[0]}")
    scale = jnp.abs(w).max(axis=0) / 7.0
    scale = jnp.where(scale == 0, 1.0, scale)
    wi = jnp.clip(jnp.round(w / scale[None, :]), -8, 7).astype(jnp.int8)
    lo = wi[0::2] & 0x0F
    hi = (wi[1::2] & 0x0F) << 4
    return ((lo | hi).astype(jnp.int8), scale)


def _quantize_w4_halves(w):
    """int4 with HALVES packing: packed row r holds in-rows r (low
    nibble) and r + in/2 (high). Single-device decoders use this
    layout so both the Pallas streaming kernel and the XLA fallback
    pair nibbles with CONTIGUOUS activation halves — the even/odd
    interleave's strided activation slices cost 1.6 ms/step at 8B.
    TP decoders keep the interleaved layout (_quantize_w4): halves
    would pair a row-shard of packed weights with two disjoint
    activation bands, which row-sharding cannot express."""
    w = jnp.asarray(w, jnp.float32)
    if w.shape[0] % 2:
        raise ValueError(f"int4 packing needs even in_features, "
                         f"got {w.shape[0]}")
    scale = jnp.abs(w).max(axis=0) / 7.0
    scale = jnp.where(scale == 0, 1.0, scale)
    wi = jnp.clip(jnp.round(w / scale[None, :]), -8, 7).astype(jnp.int8)
    half = w.shape[0] // 2
    lo = wi[:half] & 0x0F
    hi = (wi[half:] & 0x0F) << 4
    return ((lo | hi).astype(jnp.int8), scale)


def _mm(x, w, allow_kernel: bool = True):
    """x @ w where w is a dense array or a quantized (w_q, scale) pair
    (int8 full-rows, or int4 nibble-packed — told apart by the packed
    array having half the activation's in-dim). Quantized weights
    dequantize at use — the weight HBM read halves (int8) or quarters
    (int4) vs bf16, which is what memory-bound decode cares about.

    INT4 decode-shaped calls (few activation rows) route to the Pallas
    weight-streaming kernel. Only int4 does: for bf16 and int8 weights
    the kernel would put ~57 pallas dispatches into a decode step and
    break XLA's fusion around each matmul. No cell of the benchmark
    reaches the kernel, so the gate's worth is not measured on the
    chip; measure before widening it. allow_kernel=False for TP-sharded weights (the
    decoder passes mesh is None): the Mosaic call cannot be GSPMD-
    partitioned, so sharded operands would all-gather every step."""
    if isinstance(w, tuple):
        wi, scale = w
        if wi.shape[0] * 2 == x.shape[-1]:     # int4 nibble-packed
            if allow_kernel:
                from ..ops.pallas.decode_matmul import (
                    _MAX_ROWS, decode_matmul, decode_matmul_supported)
                lead = 1
                for d in x.shape[:-1]:
                    lead *= d
                if lead <= _MAX_ROWS:
                    x2 = x.reshape(lead, x.shape[-1])
                    if decode_matmul_supported(x2, w):
                        y = decode_matmul(x2, w)
                        return y.reshape(*x.shape[:-1], y.shape[-1])
            # split the CONTRACTION instead of materializing the
            # unpacked matrix; lo/hi are pure elementwise transforms
            # of the packed bytes, so XLA fuses them into the dot's
            # operand read — no [in, out] int8 intermediate in HBM.
            # allow_kernel doubles as the layout flag: single-device
            # decoders pack HALVES (contiguous activation slices), TP
            # decoders pack even/odd (row-sharding stays aligned).
            lo = ((wi << 4).astype(jnp.int8) >> 4).astype(x.dtype)
            hi = (wi >> 4).astype(x.dtype)
            half = x.shape[-1] // 2
            if allow_kernel:
                y = x[..., :half] @ lo + x[..., half:] @ hi
            else:
                y = x[..., 0::2] @ lo + x[..., 1::2] @ hi
            return y * scale.astype(x.dtype)
        return (x @ wi.astype(x.dtype)) * scale.astype(x.dtype)
    return x @ w


def _prefix_suffix_attention(q, k_suf, v_suf, k_pre, v_pre, n_cached,
                             scale: Optional[float] = None):
    """Causal attention for a SUFFIX prefill over a cached prefix.

    The suffix's queries sit at global positions ``n_cached + i``; their
    keys are the cached prefix K/V (gathered pool pages, flattened) plus
    the suffix's own K/V. Mask: every valid prefix key (position <
    n_cached) is visible to every suffix query (they all come after it),
    and the suffix-vs-suffix part is ordinary causal — which also hides
    right-padded bucket rows from real queries, exactly like the dense
    prefill's causal mask does.

    q/k_suf/v_suf: [b, s, (kv)h, d]; k_pre/v_pre: [b, kvh, P, d];
    n_cached: [b] int32. Returns [b, s, nh, d]."""
    b, s, nh, d = q.shape
    kvh = k_suf.shape[2]
    group = nh // kvh
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    p = k_pre.shape[2]
    qg = q.reshape(b, s, kvh, group, d).astype(jnp.float32)
    sp = jnp.einsum("bskgd,bkpd->bskgp", qg,
                    k_pre.astype(jnp.float32)) * scale
    pvalid = jnp.arange(p)[None] < n_cached[:, None]          # [b, p]
    sp = jnp.where(pvalid[:, None, None, None, :], sp, -1e30)
    ss = jnp.einsum("bskgd,btkd->bskgt", qg,
                    k_suf.astype(jnp.float32)) * scale
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]  # [s, t]
    ss = jnp.where(causal[None, :, None, None, :], ss, -1e30)
    probs = jax.nn.softmax(jnp.concatenate([sp, ss], axis=-1), axis=-1)
    out = jnp.einsum("bskgp,bkpd->bskgd", probs[..., :p],
                     v_pre.astype(jnp.float32)) \
        + jnp.einsum("bskgt,btkd->bskgd", probs[..., p:],
                     v_suf.astype(jnp.float32))
    return out.reshape(b, s, nh, d).astype(q.dtype)


def _gather_prefix_pages(pool, prefix_tables):
    """[num_blocks, kvh, bs, d] pool + [b, P] page ids →
    [b, kvh, P*bs, d] per-row contiguous prefix K/V. Quantized pools
    ((int8, scales) tuples — ISSUE 13) dequantize at the gather, the
    same fused read every other pool consumer uses."""
    from ..ops.paged_attention import _dequantize_gather
    # bounded, deliberate materialization: prefix_tables holds only
    # each row's OWN prefix pages (b * P_prefix, not the pool), and
    # _prefix_suffix_attention's einsum program shape needs the
    # contiguous [b, kvh, P*bs, d] block
    g = _dequantize_gather(pool, prefix_tables)  # flightcheck: disable=FC701
    b, p, kvh, bs, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, kvh, p * bs, d)


def _fuse_out(ws):
    """Concatenate weights along the OUT dim (dense arrays or
    quantized (w_q, scale) pairs with matching in-dims)."""
    if isinstance(ws[0], tuple):
        return (jnp.concatenate([w[0] for w in ws], axis=1),
                jnp.concatenate([w[1] for w in ws], axis=0))
    return jnp.concatenate(ws, axis=1)


def _extract_weights(model, weight_dtype=None, int4_halves=True):
    """Pull raw arrays out of a LlamaForCausalLM (single-device serving).
    weight_dtype='int8'/'int4' stores matmul weights quantized
    per-channel (norm/embedding stay full precision). int4_halves
    selects the packing layout (halves for single-device, even/odd
    interleave for TP row-sharding — see _quantize_w4_halves)."""
    if weight_dtype not in (None, "int8", "int4"):
        raise ValueError(f"weight_dtype must be None, 'int8' or 'int4', "
                         f"got {weight_dtype!r}")
    q = {None: lambda w: w, "int8": _quantize_w,
         "int4": _quantize_w4_halves if int4_halves
         else _quantize_w4}[weight_dtype]
    m = model.model
    layers = []
    for lyr in m.layers:
        a, mlp = lyr.self_attn, lyr.mlp
        layers.append({
            "ln1": lyr.input_layernorm.weight._value,
            "ln2": lyr.post_attention_layernorm.weight._value,
            "wq": q(a.q_proj.weight._value),
            "wk": q(a.k_proj.weight._value),
            "wv": q(a.v_proj.weight._value),
            "wo": q(a.o_proj.weight._value),
            "wg": q(mlp.gate_proj.weight._value),
            "wu": q(mlp.up_proj.weight._value),
            "wd": q(mlp.down_proj.weight._value),
        })
    head = (model.lm_head.weight._value if model.lm_head is not None
            else m.embed_tokens.weight._value.T)
    return {"embed": m.embed_tokens.weight._value, "layers": layers,
            "norm": m.norm.weight._value, "head": q(head)}


def _weight_specs(cfg):
    """(name, shape, quantized?) for every serving weight, in load
    order. Weight layout is [in, out] (the nn.Linear convention _mm
    consumes); head is [hidden, vocab] — tied-embedding models hand
    their loader embed.T."""
    hd = cfg.hidden_size // cfg.num_attention_heads
    kv = cfg.num_key_value_heads * hd
    h, it = cfg.hidden_size, cfg.intermediate_size
    specs = [("embed", (cfg.vocab_size, h), False)]
    for li in range(cfg.num_hidden_layers):
        p = f"layers.{li}."
        specs += [(p + "ln1", (h,), False), (p + "ln2", (h,), False),
                  (p + "wq", (h, h), True), (p + "wk", (h, kv), True),
                  (p + "wv", (h, kv), True), (p + "wo", (h, h), True),
                  (p + "wg", (h, it), True), (p + "wu", (h, it), True),
                  (p + "wd", (it, h), True)]
    specs += [("norm", (h,), False), ("head", (h, cfg.vocab_size), True)]
    return specs


class _TPDecoderMixin:
    """Shared fully-manual tensor-parallel machinery for the paged
    decoders (Llama and GPT expose the same mesh/mp_axis/tp_comm
    surface): canonical SpecLayout placement, the shard_map wrapper,
    the per-block reduce and the logits gather. Hosts expect
    ``self.mesh / mp_axis / tp_comm / _tp / _tp_manual / cfg /
    head_dim / weights`` to be set by their __init__."""

    @property
    def program_build_info(self) -> dict:
        """Compact build fingerprint riding every CompileWatch record
        (ISSUE 14): WHICH decoder build a compile span belongs to —
        the knobs that change compiled-program identity without
        changing operand shapes, so a trace reader can tell an int8
        pool's ragged program from an fp32 one at a glance."""
        return {
            "decoder": type(self).__name__,
            "dtype": str(np.dtype(self.weights["embed"].dtype)),
            "kv_quant": getattr(self, "kv_quant", None) or "none",
            "tp_comm": self.tp_comm if self._tp_manual else "none",
            "block_size": int(self.block_size),
        }

    def _kv_sharding(self):
        if self.mesh is None:
            return None
        # pool layout [num_blocks, kv_heads, block_size, head_dim]:
        # shard the kv-head dim (the canonical cache_k/cache_v spec)
        return self._layout().sharding(self.mesh, "cache_k")

    def _kv_scale_sharding(self):
        """Placement for the int8 pool's sidecar scales (ISSUE 13):
        [num_blocks, kv_heads, block_size] sharded over the kv-head
        dim — dim-aligned with the values' heads, so a tp shard owns
        its own scales end to end (zero collectives)."""
        if self.mesh is None:
            return None
        return self._layout().sharding(self.mesh, "cache_k_scale")

    def _kv_spec(self):
        """The shard_map spec tree for ONE pool operand: a bare
        kv-head-sharded P for dense planes, or (for kv_quant="int8")
        a per-layer list of (values spec, scales spec) tuples matching
        the (int8, scales) plane pytree leaf-for-leaf."""
        lay = self._layout()
        kv = lay.spec("cache_k")
        if getattr(self, "kv_quant", None) == "int8":
            return [(kv, lay.spec("cache_k_scale"))] \
                * self.cfg.num_hidden_layers
        return kv

    def _layout(self):
        from ..distributed.spec_layout import SpecLayout
        return SpecLayout(tp_axis=self.mp_axis)

    def _check_tp_divisibility(self, mp: int):
        """Shared TP shardability validation (Llama + GPT): attention
        heads, kv heads (where the config has them — MHA GPT configs
        don't) and the intermediate size must divide the mesh degree.
        The MANUAL shard_map path additionally needs the vocab
        divisible (its tiled logits all_gather concatenates equal
        shards); GSPMD placement tolerates uneven dims, so the legacy
        mesh= path is not held to that. int4 row-sharding (wo/wd/wf)
        shards the nibble-PACKED in-dim (in/2), which must also
        divide or device_put fails with a raw sharding error."""
        cfg = self.cfg
        kvh = getattr(cfg, "num_key_value_heads",
                      cfg.num_attention_heads)
        if (cfg.num_attention_heads % mp or kvh % mp
                or cfg.intermediate_size % mp):
            raise ValueError(
                f"TP serving needs heads ({cfg.num_attention_heads}"
                f"/{kvh}) and intermediate size "
                f"({cfg.intermediate_size}) divisible by the "
                f"'{self.mp_axis}' degree {mp}")
        if self._tp_manual and cfg.vocab_size % mp:
            raise ValueError(
                f"manual TP serving needs vocab ({cfg.vocab_size}) "
                f"divisible by the '{self.mp_axis}' degree {mp} "
                f"(the tiled logits all_gather concatenates equal "
                f"per-shard slices)")
        if self.weight_dtype == "int4" and (
                (cfg.hidden_size // 2) % mp
                or (cfg.intermediate_size // 2) % mp):
            raise ValueError(
                f"int4 TP serving needs hidden_size/2 "
                f"({cfg.hidden_size // 2}) and intermediate_size/2 "
                f"({cfg.intermediate_size // 2}) divisible by the "
                f"'{self.mp_axis}' degree {mp} (nibble-packed in-dim)")

    def tp_wrap(self, fn, n_extra: int, outs: str = "tkv",
                lora_pool: bool = False):
        """shard_map-wrap a compiled-program body of the decoder-call
        convention ``fn(weights, k_pool, v_pool, *replicated)`` for
        fully-manual tp execution: weights enter per the SpecLayout
        tree, pools sharded over the kv-head dim, everything else
        replicated. ``outs``: "tkv" for (tokens/logits, k, v) bodies,
        "takv" for the speculative verify body (tokens, accepted-mask,
        k, v — both small outputs replicated), "kv" for no-sample
        chunk bodies. ``lora_pool``: the body's convention is
        ``fn(weights, k, v, lora_pool, shard_ids, *replicated)`` —
        the adapter-page plane enters REPLICATED (every shard slices
        its own A-rows/B-columns from the full factors, so the lora
        math adds zero collectives) and ``shard_ids`` is the
        P(tp)-sharded arange whose per-shard element is the shard
        index (the repo's axis_index idiom — see pp_schedule). The
        engine uses this to wrap its sampling programs; generate()
        wraps the decoder's own."""
        from jax.sharding import PartitionSpec as P
        lay = self._layout()
        kv = self._kv_spec()
        pre = (P(None, None), P(self.mp_axis)) if lora_pool else ()
        in_specs = (lay.spec_tree(self.weights), kv, kv) + pre \
            + (P(),) * n_extra
        out_specs = {"tkv": (P(), kv, kv), "takv": (P(), P(), kv, kv),
                     "kv": (kv, kv)}[outs]
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _block_reduce(self, x):
        """The ONE collective per attention/MLP block under manual tp:
        the partial row-parallel matmul output (after wo / wd) reduces
        across shards — fp32 psum, or the EQuARX-style int8 collective
        under tp_comm="int8". Identity off tp (and on the GSPMD path,
        where the partitioner inserts the psum itself)."""
        if not self._tp_manual:
            return x
        if self.tp_comm == "int8":
            from ..distributed.collective import int8_all_reduce
            return int8_all_reduce(x, self.mp_axis, self._tp)
        return jax.lax.psum(x, self.mp_axis)

    def _gather_logits(self, logits):
        """Concatenate per-shard vocab logits (head is column-parallel)
        — the single logits collective before sampling; exact (moves
        disjoint shards) under both tp_comm modes."""
        if not self._tp_manual:
            return logits
        return jax.lax.all_gather(logits, self.mp_axis,
                                  axis=logits.ndim - 1, tiled=True)

    @property
    def _attn_dim(self) -> int:
        """Attention output width as the program sees it: the full
        hidden size, or this shard's head slice under manual tp."""
        return (self.cfg.num_attention_heads // self._tp) \
            * self.head_dim


class _SpecDecodeMixin:
    """Speculative-decoding verification tail shared by the paged
    decoders (ISSUE 9): the teacher logits at every draft position are
    just the ordinary per-row outputs of ``_ragged_logits`` — a verify
    window rides the ragged program as 1 + k extra rows of its column
    (carried token at position ctx, drafts at ctx+1..ctx+k, each with
    row_ctx = position + 1, so draft row i sees the context plus
    drafts 0..i-1, exactly the visibility the prefill-chunk rows
    already use). What the ragged program does NOT have is acceptance:
    this mixin computes the longest-accepted-prefix IN-PROGRAM and
    neutralizes the rejected tail's pool writes, so only [W] tokens and
    a [W] accepted mask ever cross the host boundary."""

    def _spec_accept(self, k_pool, v_pool, toks, draft_ids, slots,
                     seg_start, is_draft, scratch_slot: int):
        """In-program longest-accepted-prefix acceptance + rejected-
        tail KV neutralization, appended to the verify forward.

        toks [W]: this ministep's sampled per-row tokens (draft row
        r's token is the teacher's verification output for the
        position AFTER its draft). draft_ids [W]: each draft row's
        proposed token (engine-provided schedule data; non-draft rows
        hold don't-care). slots [W]: each row's flat pool slot.
        seg_start [W]: the row index of the row's column BASE (the
        carried-token row; a column's rows are contiguous, so the
        accepted prefix is a cumulative AND over (seg_start, r]).
        is_draft [W]: marks draft rows. scratch_slot: static.

        Acceptance: draft row r is accepted iff every draft in its
        column up to and including r matched the previous row's
        teacher token. Exact for greedy — each accepted token IS the
        teacher's argmax under a verified prefix.

        Neutralization: rejected draft rows already wrote K/V into
        their real slots during the forward (their keys must be
        visible to LATER draft rows — that is what verification
        conditions on). After acceptance, one zero-scatter per layer
        re-targets every row at either its own slot (rejected — junk
        zeroed) or the scratch slot (accepted / non-draft — the write
        lands in the /dev/null page, the PR-4/5 preemption mechanism).
        The host-side rollback (PagedKVCache.rollback) then rescinds
        the rejected slots so future extends re-issue them; the pool
        holds no trace of a rejected draft either way. Adds ZERO
        collectives under tp: toks are post-gather (replicated), the
        compare/cumsum is replicated, and each shard zero-scatters
        only its own kv-head slice."""
        from ..ops.paged_attention import (_plane_values,
                                           reshape_and_cache)
        ok = jnp.where(is_draft, jnp.roll(toks, 1) == draft_ids, False)
        bad = (is_draft & ~ok).astype(jnp.int32)
        cb = jnp.cumsum(bad)
        accepted = is_draft & ((cb - jnp.take(cb, seg_start)) == 0)
        tgt = jnp.where(is_draft & ~accepted, slots,
                        jnp.int32(scratch_slot))
        w = toks.shape[0]
        # tuple-aware (quantized pools): the zero-scatter goes through
        # reshape_and_cache, which quantizes zeros to exact int8 zeros
        # with unit scales — the neutralization stays bit-exact
        kp0 = _plane_values(k_pool[0])
        kvh, hd = kp0.shape[1], kp0.shape[3]
        zeros = jnp.zeros(
            (w, kvh, hd),
            jnp.float32 if isinstance(k_pool[0], tuple) else kp0.dtype)
        k_pool = list(k_pool)
        v_pool = list(v_pool)
        for li in range(len(k_pool)):
            k_pool[li], v_pool[li] = reshape_and_cache(
                zeros, zeros, k_pool[li], v_pool[li], tgt)
        return accepted, k_pool, v_pool


class _LoRAMixin:
    """Per-row LoRA deltas for the ragged serving step (ISSUE 10; the
    device half of inference/lora.py — see its module docstring for
    the paging/TP design). A decoder exposes ``lora_target_modules()``
    (ordered (name, din, dout, kind) over FULL unsharded dims; kind
    "col"/"row" mirrors the base weight's SpecLayout placement) and
    its ``_ragged_logits`` threads an optional ``lora`` context
    ``(layout, lora_flat, shard_id)`` into ``_lora_delta`` at every
    target module:

    - ``lora_flat`` [S, n_pages * page_elems]: the per-dispatch gather
      of each engine slot's adapter pages out of the shared pool
      plane (slot S-1 is the scratch row — the all-zero null adapter
      base-only and padding rows read);
    - the per-module (A [din, r], B [r, dout]) factors are STATIC
      slices of that flat vector (layout.entry — one compiled program
      serves every adapter);
    - the delta is the batched gathered matmul (S-LoRA's BGMV shape):
      rows gather their own factors by ``row_seq`` and compute
      ``(x @ A_row) @ B_row`` in f32 — zero for null rows, so mixed
      batches need no masking.

    Under manual tp, "col" modules slice B to this shard's
    out-columns (x is replicated; the delta lands on the shard's own
    output slice) and "row" modules slice A to this shard's in-rows
    (the partial delta joins the base partial product BEFORE the
    block's one allreduce) — zero extra collectives either way,
    pinned by comm_audit ``serving.ragged_lora_tp2``."""

    def lora_target_modules(self):
        raise NotImplementedError

    def _lora_delta(self, lora, row_seq, x, li: int, name: str):
        """[rows, dout_local] delta for module (li, name); x is the
        module's input activation [rows, din_local]."""
        layout, lflat, sid = lora
        offA, offB, din, dout, kind = layout.entry(li, name)
        r = layout.rank
        s = lflat.shape[0]
        A = lflat[:, offA:offA + din * r].reshape(s, din, r)
        B = lflat[:, offB:offB + r * dout].reshape(s, r, dout)
        tp = self._tp
        if tp > 1:
            if kind == "col":
                dl = dout // tp
                B = jax.lax.dynamic_slice_in_dim(B, sid * dl, dl,
                                                 axis=2)
            else:
                dl = din // tp
                A = jax.lax.dynamic_slice_in_dim(A, sid * dl, dl,
                                                 axis=1)
        Ar = jnp.take(A, row_seq, axis=0)       # [rows, din_l, r]
        Br = jnp.take(B, row_seq, axis=0)       # [rows, r, dout_l]
        xa = jnp.einsum("wd,wdr->wr", x.astype(jnp.float32), Ar)
        return jnp.einsum("wr,wro->wo", xa, Br).astype(x.dtype)


class PagedLlamaDecoder(_TPDecoderMixin, _SpecDecodeMixin, _LoRAMixin):
    """Batched paged-KV generation for a LlamaForCausalLM."""

    def __init__(self, model, num_blocks: int = 512, block_size: int = 16,
                 max_pages_per_seq: Optional[int] = None,
                 weight_dtype: Optional[str] = None, mesh=None,
                 mp_axis: str = "mp", tp_shard_map: bool = False,
                 tp_comm: str = "fp32", kv_quant: Optional[str] = None,
                 _cfg=None, _weights=None):
        cfg = model.cfg if model is not None else _cfg
        self.cfg = cfg
        self.block_size = block_size
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.max_pages = max_pages_per_seq or \
            -(-cfg.max_position_embeddings // block_size)
        self.weight_dtype = weight_dtype
        # quantized KV pool (ISSUE 13): kv_quant="int8" stores the
        # k/v planes as (int8, per-slot-per-kv-head absmax scale)
        # tuples — quantize fused into every reshape_and_cache append,
        # dequant into every pool read (attention gathers + the Pallas
        # ragged kernel's page DMA). None (the default) keeps the
        # dense planes bitwise unchanged.
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be None or 'int8', got "
                             f"{kv_quant!r}")
        self.kv_quant = kv_quant
        self.weights = (_extract_weights(model, weight_dtype,
                                         int4_halves=mesh is None)
                        if model is not None else _weights)
        self.mesh = mesh.to_jax_mesh() if hasattr(mesh, "to_jax_mesh") \
            else mesh
        self.mp_axis = mp_axis
        # tensor-parallel execution mode (ROADMAP 1): tp_shard_map runs
        # every compiled program FULLY-MANUAL under shard_map — weights
        # placed by the canonical SpecLayout table, per-shard head/
        # intermediate slices, exactly ONE allreduce per attention/MLP
        # block (after wo / wd) plus one all-gather over the per-shard
        # vocab logits. jax 0.4.x cannot lower collectives in a
        # partially-manual shard_map (the spmd_partitioner.cc:512 abort
        # partial_manual_ok() gates elsewhere); the serving tp mesh is
        # one-axis, so manual-over-every-axis is simply shard_map with
        # full in/out specs. tp_comm="int8" swaps the block allreduce
        # for the EQuARX-style quantized collective
        # (distributed.collective.int8_all_reduce); the logits gather
        # moves disjoint shards and stays exact either way.
        if tp_comm not in ("fp32", "int8"):
            raise ValueError(f"tp_comm must be 'fp32' or 'int8', got "
                             f"{tp_comm!r}")
        if tp_shard_map and self.mesh is None:
            # fail loudly: silently dropping the TP request builds an
            # unsharded decoder that OOMs one chip at 8B scale with no
            # hint why
            raise ValueError("tp_shard_map=True needs a mesh (the tp "
                             "request would otherwise be silently "
                             "dropped)")
        self.tp_comm = tp_comm
        self._tp_manual = bool(tp_shard_map) and self.mesh is not None
        if tp_comm != "fp32" and not self._tp_manual:
            raise ValueError(
                "tp_comm='int8' requires the manual shard_map path "
                "(mesh + tp_shard_map=True); on any other path the "
                "compressed collective would be silently dropped")
        self._tp = (int(self.mesh.shape[self.mp_axis])
                    if self._tp_manual else 1)
        # the Pallas decode kernel cannot be GSPMD-partitioned: only
        # unsharded (single-device) weights may route to it
        self._allow_kernel = self.mesh is None
        if self.mesh is None:
            # fuse q/k/v and gate/up along the OUT dim: decode runs
            # ~257 matmul dispatches per step at 8B, each with a fixed
            # launch cost — 4 wider matmuls per layer instead of 7.
            # TP keeps the per-projection layout _shard_weights
            # expects. The "wq" guard keeps construction idempotent
            # when a caller reuses one _weights dict across decoders.
            for lw in self.weights["layers"]:
                if "wq" in lw:
                    lw["wqkv"] = _fuse_out([lw.pop("wq"), lw.pop("wk"),
                                            lw.pop("wv")])
                    lw["wgu"] = _fuse_out([lw.pop("wg"), lw.pop("wu")])
        else:
            self._shard_weights()
        self.cache = PagedKVCache(
            num_layers=cfg.num_hidden_layers, num_blocks=num_blocks,
            block_size=block_size, kv_heads=cfg.num_key_value_heads,
            head_dim=self.head_dim,
            dtype=self.weights["embed"].dtype,
            kv_sharding=self._kv_sharding(), kv_quant=kv_quant,
            kv_scale_sharding=self._kv_scale_sharding())
        cos, sin = build_rope_cache(cfg.max_position_embeddings,
                                    self.head_dim, cfg.rope_theta,
                                    jnp.float32)
        self._cos = cos[0, :, 0, :]   # [max_len, head_dim]
        self._sin = sin[0, :, 0, :]
        if self._tp_manual:
            # generate()'s programs run fully-manual too (the engine
            # wraps its own sampling programs through tp_wrap); the
            # lambda pins the 5-arg call shape _paged_generate uses
            self._prefill = jax.jit(self.tp_wrap(
                lambda w, k, v, ids, slots:
                    self._prefill_impl(w, k, v, ids, slots),
                n_extra=2), donate_argnums=(1, 2))
            self._decode_scan = jax.jit(
                self.tp_wrap(self._decode_scan_impl, n_extra=4),
                donate_argnums=(1, 2))
        else:
            self._prefill = jax.jit(self._prefill_impl,
                                    donate_argnums=(1, 2))
            self._decode_scan = jax.jit(self._decode_scan_impl,
                                        donate_argnums=(1, 2))

    # -- lazy construction (serve 8B on one 16GB chip) ------------------------
    @classmethod
    def from_weight_loader(cls, cfg, load, num_blocks: int = 512,
                           block_size: int = 16,
                           max_pages_per_seq: Optional[int] = None,
                           weight_dtype: Optional[str] = None,
                           mesh=None, mp_axis: str = "mp",
                           tp_shard_map: bool = False,
                           tp_comm: str = "fp32",
                           kv_quant: Optional[str] = None):
        """Build a decoder WITHOUT materializing the full-precision
        model: llama_3_8b bf16 is ~16 GB — the whole of a v5e's HBM —
        but its int4 weights are ~4 GB. `load(name, shape)` returns the
        raw [in, out] array for one weight (names: 'embed', 'norm',
        'head', 'layers.{i}.{ln1,ln2,wq,wk,wv,wo,wg,wu,wd}' — see
        _weight_specs); each matmul weight is quantized on device as it
        arrives and the full-precision original dropped, so peak HBM ~=
        quantized total + one decoder layer of bf16. Works with any
        shard-at-a-time checkpoint reader. Reference analog: the
        load-then-optimize predictor pipeline
        (/root/reference/paddle/fluid/inference/api/
        analysis_predictor.h:100)."""
        if weight_dtype not in (None, "int8", "int4"):
            raise ValueError(f"weight_dtype must be None, 'int8' or "
                             f"'int4', got {weight_dtype!r}")
        qf = {None: jnp.asarray, "int8": _quantize_w,
              "int4": (_quantize_w4_halves if mesh is None
                       else _quantize_w4)}[weight_dtype]
        layers = [dict() for _ in range(cfg.num_hidden_layers)]
        flat = {}
        for name, shape, is_mat in _weight_specs(cfg):
            arr = load(name, shape)
            if tuple(arr.shape) != tuple(shape):
                raise ValueError(f"loader returned {arr.shape} for "
                                 f"{name}; expected {shape}")
            val = qf(arr) if is_mat else jnp.asarray(arr)
            if name.startswith("layers."):
                _, li, key = name.split(".")
                layers[int(li)][key] = val
            else:
                flat[name] = val
            del arr
            if name.endswith(("wd", "head", "embed")):
                # throttle once per layer: force the queued quantizes
                # to finish so full-precision temporaries never pile up
                # in HBM ahead of the device stream
                leaf = val[0] if isinstance(val, tuple) else val
                np.asarray(jax.device_get(leaf.ravel()[:1]))
        weights = {"embed": flat["embed"], "layers": layers,
                   "norm": flat["norm"], "head": flat["head"]}
        return cls(None, num_blocks=num_blocks, block_size=block_size,
                   max_pages_per_seq=max_pages_per_seq,
                   weight_dtype=weight_dtype, mesh=mesh,
                   mp_axis=mp_axis, tp_shard_map=tp_shard_map,
                   tp_comm=tp_comm, kv_quant=kv_quant, _cfg=cfg,
                   _weights=weights)

    @classmethod
    def from_config(cls, cfg, seed: int = 0, init_scale: float = 0.02,
                    **kw):
        """Randomly-initialized decoder straight from a config — the
        serving-bench path for geometries whose full-precision weights
        exceed HBM, and the quickest way to exercise a pool/engine
        layout. Norm gains init to ones; everything else N(0, scale)."""
        import zlib
        base = jax.random.PRNGKey(seed)
        dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32

        def load(name, shape):
            if len(shape) == 1:            # rms_norm gains
                return jnp.ones(shape, dtype)
            k = jax.random.fold_in(
                base, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            return jax.random.normal(k, shape, dtype) * init_scale

        return cls.from_weight_loader(cfg, load, **kw)

    # -- tensor-parallel serving -----------------------------------------------
    # Reference analog: the FleetExecutor serving DAG
    # (/root/reference/paddle/fluid/distributed/fleet_executor/
    # fleet_executor.h:36). TPU-native: NamedShardings on weights + KV
    # pool; GSPMD partitions the jitted prefill/decode programs (heads
    # shard over the mp axis, o/down projections reduce via psum).
    def _shard_weights(self):
        """Place the weight tree via the canonical SpecLayout table —
        the SAME table flightcheck's FC605 parses, so placement cannot
        drift from what static analysis pins. strict: every key of the
        serving vocabulary must have a canonical spec (a silently
        replicated weight is how an implicit all-gather starts)."""
        self._check_tp_divisibility(int(self.mesh.shape[self.mp_axis]))
        self.weights = self._layout().apply(self.mesh, self.weights,
                                            strict=True)

    # -- attention building blocks -----------------------------------------
    def _proj_qkv(self, w, hn, b, s):
        cfg = self.cfg
        # under manual tp the program runs on per-shard arrays: this
        # shard's head slice (column-parallel wq/wk/wv). tp divides
        # kvh, so every shard holds whole GQA groups and the q->kv
        # head mapping is the global one restricted to the slice.
        nh, kvh, hd = (cfg.num_attention_heads // self._tp,
                       cfg.num_key_value_heads // self._tp,
                       self.head_dim)
        if "wqkv" in w:
            qkv = _mm(hn, w["wqkv"], self._allow_kernel)
            q, k, v = jnp.split(
                qkv, [nh * hd, nh * hd + kvh * hd], axis=-1)
            return (q.reshape(b, s, nh, hd), k.reshape(b, s, kvh, hd),
                    v.reshape(b, s, kvh, hd))
        q = _mm(hn, w["wq"], self._allow_kernel).reshape(b, s, nh, hd)
        k = _mm(hn, w["wk"], self._allow_kernel).reshape(b, s, kvh, hd)
        v = _mm(hn, w["wv"], self._allow_kernel).reshape(b, s, kvh, hd)
        return q, k, v

    def _mlp(self, w, hn):
        ak = self._allow_kernel
        if "wgu" in w:
            gu = _mm(hn, w["wgu"], ak)
            g_, u_ = jnp.split(gu, [self.cfg.intermediate_size],
                               axis=-1)
            return _mm(jax.nn.silu(g_) * u_, w["wd"], ak)
        return _mm(jax.nn.silu(_mm(hn, w["wg"], ak))
                   * _mm(hn, w["wu"], ak), w["wd"], ak)

    def lora_target_modules(self):
        cfg = self.cfg
        h = cfg.hidden_size
        ad = cfg.num_attention_heads * self.head_dim
        kvd = cfg.num_key_value_heads * self.head_dim
        it = cfg.intermediate_size
        return (("wq", h, ad, "col"), ("wk", h, kvd, "col"),
                ("wv", h, kvd, "col"), ("wo", ad, h, "row"),
                ("wg", h, it, "col"), ("wu", h, it, "col"),
                ("wd", it, h, "row"))

    def _lora_mlp(self, w, hn, lora, row_seq, li):
        """The _mlp body with per-row LoRA deltas on gate/up/down —
        kept separate so the base path's fused program is untouched.
        Deltas add to the PRE-activation projections (W -> W + s*AB);
        the wd delta joins the partial product before the block's
        allreduce (see _LoRAMixin)."""
        ak = self._allow_kernel
        if "wgu" in w:
            gu = _mm(hn, w["wgu"], ak)
            g_, u_ = jnp.split(gu, [self.cfg.intermediate_size],
                               axis=-1)
        else:
            g_ = _mm(hn, w["wg"], ak)
            u_ = _mm(hn, w["wu"], ak)
        g_ = g_ + self._lora_delta(lora, row_seq, hn, li, "wg")
        u_ = u_ + self._lora_delta(lora, row_seq, hn, li, "wu")
        mid = jax.nn.silu(g_) * u_
        return _mm(mid, w["wd"], ak) \
            + self._lora_delta(lora, row_seq, mid, li, "wd")

    def _rope(self, x, positions):
        # x [b, s, h, d]; positions [b, s]
        cos = self._cos[positions][:, :, None, :].astype(x.dtype)
        sin = self._sin[positions][:, :, None, :].astype(x.dtype)
        return x * cos + _rotate_half(x) * sin

    # -- compiled programs ---------------------------------------------------
    def _prefill_impl(self, weights, k_pool, v_pool, ids, slots,
                      last_idx=None):
        """ids [b, s]; slots [b, s] flat page slots; last_idx [b] index
        of each sequence's final REAL token (defaults to s-1 — bucketed
        right-padded prompts pass the real length). Returns (logits at
        last_idx [b, vocab], updated pools)."""
        cfg = self.cfg
        b, s = ids.shape
        h = jnp.take(weights["embed"], ids, axis=0)
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        flat = slots.reshape(-1)
        for li, w in enumerate(weights["layers"]):
            hn = rms_norm(h, w["ln1"], cfg.rms_norm_eps)
            q, k, v = self._proj_qkv(w, hn, b, s)
            q = self._rope(q, positions)
            k = self._rope(k, positions)
            attn = flash_attention(q, k, v, causal=True)
            h = h + self._block_reduce(
                _mm(attn.reshape(b, s, self._attn_dim), w["wo"],
                    self._allow_kernel))
            hn = rms_norm(h, w["ln2"], cfg.rms_norm_eps)
            h = h + self._block_reduce(self._mlp(w, hn))
            # scatter this layer's k/v into the pool pages (list swap —
            # no stacked-pool slice copies)
            from ..ops.paged_attention import reshape_and_cache
            nk, nv = reshape_and_cache(
                k.reshape(b * s, -1, self.head_dim),
                v.reshape(b * s, -1, self.head_dim),
                k_pool[li], v_pool[li], flat)
            k_pool = list(k_pool)
            v_pool = list(v_pool)
            k_pool[li] = nk
            v_pool[li] = nv
        h = rms_norm(h, weights["norm"], cfg.rms_norm_eps)
        if last_idx is None:
            hl = h[:, -1]
        else:
            hl = h[jnp.arange(b), last_idx]
        logits = self._gather_logits(
            _mm(hl, weights["head"],
                self._allow_kernel).astype(jnp.float32))
        return logits, k_pool, v_pool

    def _prefill_prefix_impl(self, weights, k_pool, v_pool, ids, slots,
                             last_idx, n_cached, prefix_tables):
        """SUFFIX prefill for prefix-cache hits: `ids` [b, s] holds each
        row's uncovered suffix (right-padded to the bucket), `n_cached`
        [b] the tokens already sitting in the pool, and `prefix_tables`
        [b, P] the physical pages holding them (scratch-padded past the
        row's prefix). RoPE positions are offset by n_cached (data, not
        shape — one compiled program serves every hit length) and every
        layer attends over [gathered prefix pages ++ suffix]. Rows with
        n_cached == 0 degenerate to the ordinary bucketed prefill.
        Returns (logits at last_idx [b, vocab], updated pools)."""
        cfg = self.cfg
        b, s = ids.shape
        h = jnp.take(weights["embed"], ids, axis=0)
        # clamp like the GPT twin: a recompute tail chunk's pad
        # positions can pass max_position_embeddings; the RoPE table
        # gather would clamp implicitly, but the bound is part of the
        # program's contract — make it explicit
        positions = jnp.minimum(
            jnp.arange(s)[None] + n_cached[:, None],
            cfg.max_position_embeddings - 1)              # [b, s]
        flat = slots.reshape(-1)
        for li, w in enumerate(weights["layers"]):
            hn = rms_norm(h, w["ln1"], cfg.rms_norm_eps)
            q, k, v = self._proj_qkv(w, hn, b, s)
            q = self._rope(q, positions)
            k = self._rope(k, positions)
            k_pre = _gather_prefix_pages(k_pool[li], prefix_tables)
            v_pre = _gather_prefix_pages(v_pool[li], prefix_tables)
            attn = _prefix_suffix_attention(q, k, v, k_pre, v_pre,
                                            n_cached)
            h = h + self._block_reduce(
                _mm(attn.reshape(b, s, self._attn_dim), w["wo"],
                    self._allow_kernel))
            hn = rms_norm(h, w["ln2"], cfg.rms_norm_eps)
            h = h + self._block_reduce(self._mlp(w, hn))
            from ..ops.paged_attention import reshape_and_cache
            nk, nv = reshape_and_cache(
                k.reshape(b * s, -1, self.head_dim),
                v.reshape(b * s, -1, self.head_dim),
                k_pool[li], v_pool[li], flat)
            k_pool = list(k_pool)
            v_pool = list(v_pool)
            k_pool[li] = nk
            v_pool[li] = nv
        h = rms_norm(h, weights["norm"], cfg.rms_norm_eps)
        hl = h[jnp.arange(b), last_idx]
        logits = self._gather_logits(
            _mm(hl, weights["head"],
                self._allow_kernel).astype(jnp.float32))
        return logits, k_pool, v_pool

    def _prefill_chunk_impl(self, weights, k_pool, v_pool, ids, slots,
                            n_cached, prefix_tables):
        """One MID-PROMPT prefill chunk (chunked prefill): the
        suffix-prefill attention of _prefill_prefix_impl at offset
        n_cached — chunk i of a long prompt prefills with chunks
        0..i-1's pages riding along as the prefix table, exactly like
        a prefix-cache hit — but intermediate chunks only write K/V:
        no last-token logits exist until the FINAL chunk. Jitting this
        wrapper lets XLA dead-code-eliminate the head matmul and the
        logit gather, and the engine's no-sample dispatch consumes no
        PRNG key (so chunked and monolithic prefill share one key
        stream for a solo request). n_cached need NOT be block-aligned:
        the prefix gather fetches whole pages and masks positions >=
        n_cached, so a chunk boundary may land mid-page.
        Returns (k_pool, v_pool)."""
        _, k_pool, v_pool = self._prefill_prefix_impl(
            weights, k_pool, v_pool, ids, slots,
            jnp.zeros(ids.shape[0], jnp.int32), n_cached, prefix_tables)
        return k_pool, v_pool

    def _decode_logits(self, weights, k_pool, v_pool, last_ids, tables,
                       ctx_lens, slots):
        """One decode token for the batch, up to the logits (shared by
        the greedy body and the serving engine's sampling step).
        last_ids [b]; tables [b, max_pages]; ctx_lens [b] (tokens
        already cached, EXCLUDING this one); slots [b] flat slot for
        this token's k/v."""
        cfg = self.cfg
        b = last_ids.shape[0]
        h = jnp.take(weights["embed"], last_ids, axis=0)  # [b, d]
        pos = ctx_lens[:, None]                            # [b, 1]
        for li, w in enumerate(weights["layers"]):
            hn = rms_norm(h, w["ln1"], cfg.rms_norm_eps)
            q, k, v = self._proj_qkv(w, hn[:, None, :], b, 1)
            q = self._rope(q, pos)[:, 0]                   # [b, nh, d]
            k = self._rope(k, pos)[:, 0]                   # [b, kvh, d]
            v = v[:, 0]
            from ..ops.paged_attention import reshape_and_cache
            kp, vp = reshape_and_cache(k, v, k_pool[li], v_pool[li],
                                       slots)
            k_pool = list(k_pool)
            v_pool = list(v_pool)
            k_pool[li] = kp
            v_pool[li] = vp
            attn = paged_attention_decode(q, kp, vp, tables, ctx_lens + 1)
            h = h + self._block_reduce(
                _mm(attn.reshape(b, self._attn_dim), w["wo"],
                    self._allow_kernel))
            hn = rms_norm(h, w["ln2"], cfg.rms_norm_eps)
            h = h + self._block_reduce(self._mlp(w, hn))
        h = rms_norm(h, weights["norm"], cfg.rms_norm_eps)
        logits = self._gather_logits(
            _mm(h, weights["head"],
                self._allow_kernel).astype(jnp.float32))
        return logits, k_pool, v_pool

    def _ragged_logits(self, weights, k_pool, v_pool, ids, positions,
                       slots, row_seq, row_ctx, tables, lora=None):
        """One RAGGED ministep up to the logits: a flattened token
        batch mixing decode rows (one token of a running sequence) and
        no-sample prefill-chunk rows (consecutive prompt positions),
        no [max_batch] padding — the serving engine's unified
        one-program-per-step path. ids/positions/slots/row_seq/row_ctx
        [rows]; tables [num_seqs, max_pages] (a shared per-slot table,
        scratch row included). Every row's K/V is written to the pool
        at its flat slot BEFORE attention, so intra-call causality is
        pure data: row_ctx bounds what each row sees (see
        ops.paged_attention.ragged_paged_attention_reference).
        ``lora``: optional (layout, lora_flat, shard_id) multi-tenant
        context — per-row adapter deltas at every target module, null
        rows reading the scratch slot's zero page (_LoRAMixin); the
        base path's program is byte-identical when None.
        Returns (logits [rows, vocab], k_pool, v_pool)."""
        cfg = self.cfg
        r = ids.shape[0]
        # the named scopes are LlamaForCausalLM's, so a device trace of
        # either hot path groups by the same names
        with jax.named_scope("embed"):
            h = jnp.take(weights["embed"], ids, axis=0)    # [r, d]
        # clamp like the chunked-prefill programs: pad rows of a tail
        # chunk may carry positions past max_position_embeddings
        pos = jnp.minimum(positions,
                          cfg.max_position_embeddings - 1)[:, None]
        k_pool = list(k_pool)
        v_pool = list(v_pool)
        for li, w in enumerate(weights["layers"]):
            with jax.named_scope(f"layer{li}/attn"):
                hn = rms_norm(h, w["ln1"], cfg.rms_norm_eps)
                q, k, v = self._proj_qkv(w, hn[:, None, :], r, 1)
                if lora is not None:
                    q = q + self._lora_delta(lora, row_seq, hn, li,
                                             "wq").reshape(q.shape)
                    k = k + self._lora_delta(lora, row_seq, hn, li,
                                             "wk").reshape(k.shape)
                    v = v + self._lora_delta(lora, row_seq, hn, li,
                                             "wv").reshape(v.shape)
                q = self._rope(q, pos)[:, 0]               # [r, nh, d]
                k = self._rope(k, pos)[:, 0]               # [r, kvh, d]
                v = v[:, 0]
                from ..ops.paged_attention import reshape_and_cache
                kp, vp = reshape_and_cache(k, v, k_pool[li], v_pool[li],
                                           slots)
                k_pool[li] = kp
                v_pool[li] = vp
                attn = ragged_paged_attention(q, kp, vp, tables, row_seq,
                                              row_ctx)
                af = attn.reshape(r, self._attn_dim)
                o = _mm(af, w["wo"], self._allow_kernel)
                if lora is not None:
                    o = o + self._lora_delta(lora, row_seq, af, li, "wo")
                h = h + self._block_reduce(o)
            with jax.named_scope(f"layer{li}/mlp"):
                hn = rms_norm(h, w["ln2"], cfg.rms_norm_eps)
                mlp = self._mlp(w, hn) if lora is None \
                    else self._lora_mlp(w, hn, lora, row_seq, li)
                h = h + self._block_reduce(mlp)
        with jax.named_scope("final_norm"):
            h = rms_norm(h, weights["norm"], cfg.rms_norm_eps)
        with jax.named_scope("lm_head"):
            logits = self._gather_logits(
                _mm(h, weights["head"],
                    self._allow_kernel).astype(jnp.float32))
        return logits, k_pool, v_pool

    def _decode_body(self, weights, k_pool, v_pool, last_ids, tables,
                     ctx_lens, slots):
        """Greedy single decode token (the scanned batch path)."""
        logits, k_pool, v_pool = self._decode_logits(
            weights, k_pool, v_pool, last_ids, tables, ctx_lens, slots)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, k_pool, v_pool

    def _decode_scan_impl(self, weights, k_pool, v_pool, first_ids,
                          tables_all, ctx_all, slots_all):
        """The WHOLE decode loop as one compiled lax.scan — one dispatch
        for T tokens (the page/slot schedule is deterministic, so the
        host precomputes it). Essential when per-dispatch latency is
        high; also the canonical TPU shape for the serving loop."""
        def step(carry, xs):
            last_ids, kp, vp = carry
            tables, ctx, slots = xs
            nxt, kp, vp = self._decode_body(weights, kp, vp, last_ids,
                                            tables, ctx, slots)
            return (nxt, kp, vp), nxt
        (_, k_pool, v_pool), toks = jax.lax.scan(
            step, (first_ids, k_pool, v_pool),
            (tables_all, ctx_all, slots_all))
        return toks.swapaxes(0, 1), k_pool, v_pool   # [b, T]

    # -- public API ----------------------------------------------------------
    def generate(self, input_ids, max_new_tokens: int = 32,
                 timings: dict = None):
        """Greedy batched generation. input_ids [b, prompt_len] (np /
        Tensor), EQUAL-length prompts (mixed lengths are the
        ServingEngine's job — its bucketed admission right-pads onto a
        scratch page); returns np.ndarray [b, prompt_len +
        max_new_tokens]. When `timings` is a dict it receives
        prefill_s / decode_s wall times."""
        return _paged_generate(self, input_ids, max_new_tokens, timings)


def _paged_generate(dec, input_ids, max_new_tokens, timings=None):
    """Shared batch-generate engine for the paged decoders (Llama and
    GPT expose the same .cache/._prefill/._decode_scan surface): page
    allocation, ONE compiled prefill, host-precomputed decode schedule,
    ONE compiled scan, page free."""
    import time as _time
    # under manual tp, schedule arrays go in as UNCOMMITTED host
    # arrays: jnp.asarray would commit them to the default device,
    # which conflicts with the tp mesh the program runs on
    aj = np.asarray if getattr(dec, "_tp", 1) > 1 else jnp.asarray
    ids = input_ids._value if isinstance(input_ids, Tensor) \
        else jnp.asarray(input_ids)
    ids = np.asarray(ids).astype(np.int32)
    b, s = ids.shape
    cache = dec.cache
    seqs = list(range(b))
    slot_rows = []
    for i in seqs:
        cache.allocate(i, s + max_new_tokens)
        slot_rows.append([cache.extend(i) for _ in range(s)])
    slots = aj(np.asarray(slot_rows, np.int32))
    t0 = _time.perf_counter()
    logits, cache.k, cache.v = dec._prefill(
        dec.weights, cache.k, cache.v, aj(ids), slots)
    next_ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if timings is not None:
        next_ids.block_until_ready()
        timings["prefill_s"] = _time.perf_counter() - t0

    if max_new_tokens <= 0:
        for i in seqs:
            cache.free(i)
        return ids
    # precompute the whole schedule host-side (deterministic), then
    # run ONE compiled scan for all remaining tokens
    T = max_new_tokens - 1
    ctx_all = np.zeros((T, b), np.int32)
    slots_all = np.zeros((T, b), np.int32)
    tables_all = np.zeros((T, b, dec.max_pages), np.int32)
    for t in range(T):
        ctx_all[t] = [cache.context_len(i) for i in seqs]
        slots_all[t] = [cache.extend(i) for i in seqs]
        tables_all[t] = np.stack(
            [cache.block_table(i, dec.max_pages) for i in seqs])
    t1 = _time.perf_counter()
    if T > 0:
        toks, cache.k, cache.v = dec._decode_scan(
            dec.weights, cache.k, cache.v, next_ids,
            aj(tables_all), aj(ctx_all), aj(slots_all))
        toks = np.asarray(toks)
    else:
        toks = np.zeros((b, 0), np.int32)
    if timings is not None:
        timings["decode_s"] = _time.perf_counter() - t1
    for i in seqs:
        cache.free(i)
    return np.concatenate(
        [ids, np.asarray(next_ids)[:, None], toks], axis=1)
