"""Paged (block) KV-cache attention — the serving decode path.

Reference: block_multi_head_attention
(/root/reference/paddle/phi/kernels/fusion/gpu/block_multi_head_attention
kernel + python/paddle/incubate/nn/functional/block_multihead_attention.py):
the KV cache lives in fixed-size blocks; a per-sequence block table maps
logical positions to physical blocks, so sequences grow without
reallocation and memory fragments are reclaimed per-block (vLLM-style).

TPU-native: on TPU the decode runs a Pallas kernel
(ops/pallas/paged_attention.py) whose K/V BlockSpec index maps consume a
scalar-prefetched block table — each grid step DMAs one physical page
from the HBM pool, no gathered [batch, window, ...] materialization, with
an online-softmax accumulated across pages in VMEM scratch. The jnp.take
composition below is the reference oracle + CPU path; everything is
fixed-shape (max_blocks per sequence) so one compiled program serves all
lengths, with masking by context length.
"""
from __future__ import annotations

from collections import Counter, OrderedDict
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["KVCacheExhausted", "PagedKVCache", "paged_attention_decode",
           "paged_attention_decode_reference", "paged_attention_impl",
           "quantize_kv_rows",
           "ragged_paged_attention", "ragged_paged_attention_reference",
           "reshape_and_cache"]


# ---------------------------------------------------------------------------
# Quantized KV pool (ISSUE 13): a pool plane is either a dense array
# [num_blocks, kv_heads, block_size, head_dim] (fp32/bf16 — the
# original layout, bitwise unchanged) or an (int8 values, f32 scales)
# TUPLE with the scales in a per-slot-per-kv-head sidecar plane
# [num_blocks, kv_heads, block_size] — one absmax scale per written
# K/V row per head, living inside the page so the Pallas kernel's
# per-physical-page DMA fetches values + scales together. The tuple
# rides every existing pytree path (jit args, donation, shard_map
# specs, lax.scan carries) without new plumbing: quantize is fused
# into reshape_and_cache (the only pool write), dequant into the
# attention gathers (the only pool reads).
# ---------------------------------------------------------------------------

def _plane_values(plane):
    """The value array of a pool plane (tuple-aware)."""
    return plane[0] if isinstance(plane, tuple) else plane


def quantize_kv_rows(x):
    """Per-row-per-kv-head symmetric absmax int8 for a K/V append
    batch ``x`` [n, kv_heads, head_dim] (same math as the weight
    quantizer _quantize_w, but over the head_dim axis — each written
    slot carries its own scale, so appending never re-scales already
    written tokens and a page mixes tokens of any magnitude).
    Returns (int8 [n, kv_heads, head_dim], f32 scales [n, kv_heads])."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127) \
        .astype(jnp.int8)
    return q, scale


def _dequantize_gather(plane, idx):
    """jnp.take over a pool plane's leading (page) axis with dequant
    fused at the gather: tuple planes come back as f32
    values * per-slot scales, dense planes gather as-is.

    mode="clip": unused table slots hold sentinel page ids; the
    default out-of-bounds mode fills float gathers with NaN, which a
    downstream mask multiplies to NaN, not zero. Clipped reads land on
    a real page and the per-position mask discards them."""
    if isinstance(plane, tuple):
        vals, scales = plane
        return jnp.take(vals, idx, axis=0, mode="clip") \
            .astype(jnp.float32) \
            * jnp.take(scales, idx, axis=0, mode="clip")[..., None]
    return jnp.take(plane, idx, axis=0, mode="clip")


class KVCacheExhausted(RuntimeError):
    """The block pool cannot satisfy an allocation — free list dry and
    nothing evictable. A RuntimeError subclass so pre-existing callers
    catching RuntimeError keep working; the ServingEngine catches THIS
    type specifically to trigger preemption-with-recompute instead of
    failing the request. The chaos harness (utils/chaos.py) raises it
    from the allocator fault hook to simulate pool pressure."""


def reshape_and_cache(k, v, k_cache, v_cache, slot_mapping):
    """Scatter this step's K/V ([batch, kv_heads, head_dim]) into the
    block pool at flat slot ids (block_id * block_size + offset).
    Returns updated caches. Cache layout: [num_blocks, kv_heads,
    block_size, head_dim] — a physical page is one contiguous
    [kv_heads, block_size, head_dim] region, so the Pallas decode kernel
    fetches a whole page (all kv heads) with a single DMA.

    Quantized pools (kv_quant="int8"): a cache passed as an
    (int8 values, f32 scales) tuple gets the QUANTIZE FUSED INTO THE
    APPEND — per-row-per-kv-head absmax int8 plus a scale scatter into
    the sidecar plane, one functional update each, no fp32 staging
    copy of the pool. Under tp the per-shard kv-head slice quantizes
    its own heads with its own scales, so the append path stays at
    zero collectives on the quantized layout too."""
    if isinstance(k_cache, tuple):
        kc, kcs = k_cache
        vc, vcs = v_cache
        nb, h, bs, d = kc.shape
        blocks = slot_mapping // bs
        offs = slot_mapping % bs
        heads = jnp.arange(h)[None, :]
        kq, ks = quantize_kv_rows(k)
        vq, vs = quantize_kv_rows(v)
        kc = kc.at[blocks[:, None], heads, offs[:, None]].set(kq)
        kcs = kcs.at[blocks[:, None], heads, offs[:, None]].set(ks)
        vc = vc.at[blocks[:, None], heads, offs[:, None]].set(vq)
        vcs = vcs.at[blocks[:, None], heads, offs[:, None]].set(vs)
        return (kc, kcs), (vc, vcs)
    nb, h, bs, d = k_cache.shape
    blocks = slot_mapping // bs
    offs = slot_mapping % bs
    heads = jnp.arange(h)[None, :]
    k_cache = k_cache.at[blocks[:, None], heads, offs[:, None]].set(k)
    v_cache = v_cache.at[blocks[:, None], heads, offs[:, None]].set(v)
    return k_cache, v_cache


def paged_attention_decode_reference(q, k_cache, v_cache, block_tables,
                                     context_lens,
                                     scale: Optional[float] = None):
    """One-token decode attention over the paged cache (jnp oracle).

    q:            [batch, num_heads, head_dim]  (this step's query)
    k_cache/v_cache: [num_blocks, kv_heads, block_size, head_dim]
                  (or (int8, scales) tuples — dequant at the gather)
    block_tables: [batch, max_blocks] int32 physical block ids
    context_lens: [batch] int32 — valid tokens per sequence (incl. this)
    Returns [batch, num_heads, head_dim].
    """
    # A pure decode batch is the ragged program with one row per
    # sequence and the identity row->table mapping; delegating reuses
    # the online-softmax page walk, so the dense oracle no longer
    # materializes every row's whole [max_blocks * bs] K/V (the flat
    # _dequantize_gather this function used to do — FC701's
    # pool-traffic class; a decode row always has context_lens >= 1,
    # so the refs agree everywhere the dense path is defined).
    b = q.shape[0]
    return ragged_paged_attention_reference(
        q, k_cache, v_cache, block_tables,
        jnp.arange(b, dtype=jnp.int32), context_lens, scale)


def ragged_paged_attention_reference(q, k_cache, v_cache, block_tables,
                                     row_seq, row_ctx,
                                     scale: Optional[float] = None):
    """Ragged mixed prefill+decode attention over the paged cache (jnp
    oracle + CPU path).

    One call covers a FLATTENED token batch mixing rows from many
    sequences — decode rows (one token of a running sequence) and
    prefill-chunk rows (consecutive prompt positions of a prefilling
    sequence) side by side, no [max_batch] padding:

    q:            [total_rows, num_heads, head_dim]
    k_cache/v_cache: [num_blocks, kv_heads, block_size, head_dim]
    block_tables: [num_seqs, max_pages] int32 physical page ids
    row_seq:      [total_rows] int32 — which table row each q row reads
    row_ctx:      [total_rows] int32 — keys VISIBLE to the row: pool
                  positions < row_ctx attend (the row's own K/V is
                  already in the pool, so a decode row passes ctx+1 and
                  chunk row j of a prefill at offset `off` passes
                  off+j+1 — that per-row bound IS the causal mask
                  between same-sequence rows of one call; speculative
                  DRAFT row i of a verify window rides the same
                  contract at ctx+i+1, so it sees the context, the
                  column's carried token, drafts 0..i-1 and itself —
                  never a later draft)
    Rows with row_ctx <= 0 (grid padding) return exact zeros.
    Quantized pools ((int8, scales) tuples) dequantize INSIDE the page
    walk — the per-page gather fetches values + sidecar scales and
    multiplies before the score matmul, exactly the Pallas kernel's
    fused per-page-DMA dequant, so the oracle stays the kernel's
    ground truth on the int8 layout too.
    Returns [total_rows, num_heads, head_dim].
    """
    r, nh, d = q.shape
    nb, kvh, bs, _ = _plane_values(k_cache).shape
    max_pages = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(d)
    group = nh // kvh

    tables_r = jnp.take(block_tables, row_seq, axis=0)   # [r, P]
    qg = q.reshape(r, kvh, group, d).astype(jnp.float32)
    ctx = row_ctx[:, None, None, None]

    # ONLINE softmax over a page walk — the kernel's structure, not
    # just its math: a one-page-per-iteration gather keeps peak memory
    # at [r, kvh, bs, d] instead of materializing every row's whole
    # [max_pages * bs] K/V (a wide idle-drain ragged program has
    # hundreds of rows — a flat gather is gigabytes of traffic per
    # layer on the CPU path), and the trip count is bounded by the
    # batch's LONGEST visible context, not the table width (the
    # kernel's n_pages bound; a traced fori_loop limit, so short-row
    # batches in a long-bucket table skip the empty tail). Masking is
    # per position (pos < row_ctx); fully-masked rows keep l == 0 and
    # come out EXACTLY zero below, matching the Pallas kernel's guard,
    # instead of averaging V over a uniform distribution.
    def page_step(p, carry):
        m_prev, l_prev, acc = carry
        pids = jnp.take(tables_r, p, axis=1)             # [r]
        k = _dequantize_gather(k_cache, pids).astype(jnp.float32)
        v = _dequantize_gather(v_cache, pids)            # [r, kvh, bs, d]
        sc = jnp.einsum("rkgd,rksd->rkgs", qg, k) * scale
        pos = p * bs + jnp.arange(bs)[None, None, None, :]
        mask = pos < ctx
        sc = jnp.where(mask, sc, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        prob = jnp.where(mask, jnp.exp(sc - m_new[..., None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(prob, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "rkgs,rksd->rkgd", prob, v.astype(jnp.float32))
        return m_new, l_new, acc

    n_pages = jnp.minimum((jnp.max(row_ctx) + bs - 1) // bs, max_pages)
    m, l, acc = jax.lax.fori_loop(
        0, n_pages, page_step,
        (jnp.full((r, kvh, group), -1e30, jnp.float32),
         jnp.zeros((r, kvh, group), jnp.float32),
         jnp.zeros((r, kvh, group, d), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(r, nh, d).astype(q.dtype)


def ragged_paged_attention(q, k_cache, v_cache, block_tables, row_seq,
                           row_ctx, scale: Optional[float] = None):
    """Ragged mixed prefill+decode attention; Pallas scalar-prefetch
    kernel where paged_attention_impl admits it, jnp oracle elsewhere
    (CPU, FLAGS.use_pallas_kernels=False, head_dim not a multiple of
    128, quantized pools). The kernel's dequant-in-VMEM path for
    (int8, scales) pools is kept and interpret-tested, but the chip's
    compiler refuses its sidecar slice, so the gate keeps such pools
    off it (ROADMAP S3)."""
    if _pallas_decode_ok(q, k_cache):
        from .pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        return ragged_paged_attention_pallas(q, k_cache, v_cache,
                                             block_tables, row_seq,
                                             row_ctx, scale)
    return ragged_paged_attention_reference(q, k_cache, v_cache,
                                            block_tables, row_seq,
                                            row_ctx, scale)


class PagedKVCache:
    """Host-side block allocator + device block pool (the cache manager
    half of the reference's block_multihead_attention serving path).

    One instance per layer set: caches are stacked [num_layers, ...] so a
    decode step updates all layers functionally.

    Automatic prefix caching (vLLM-style): every block carries a ref
    count, and FULL blocks whose token content is known get a chain hash
    ``hash(parent_hash, block_tokens)`` registered in a hash→block
    index. Because full blocks are immutable once written, a new request
    whose prompt shares a block-aligned prefix with previously seen
    content can splice the physical blocks into its table
    (``allocate_with_prefix``) instead of re-prefilling — a ref-count
    bump, no copy. Freed blocks that still carry a valid hash are PARKED
    in an LRU of cached-but-unreferenced blocks rather than zeroed; they
    are only truly evicted (hash invalidated) when the free list runs
    dry, so hot prefixes survive across requests at zero capacity cost.
    """

    def __init__(self, num_layers: int, num_blocks: int, block_size: int,
                 kv_heads: int, head_dim: int, dtype=jnp.float32,
                 kv_sharding=None, kv_quant=None,
                 kv_scale_sharding=None):
        if kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant must be None or 'int8', got {kv_quant!r}")
        self.num_layers = num_layers
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.kv_quant = kv_quant
        # per-layer pools as a LIST pytree: updating one layer swaps a
        # list element — no [L, ...] slice/update copies in the compiled
        # decode step. kv_sharding (a NamedSharding over the kv-head
        # dim) places the pool for tensor-parallel serving.
        # kv_quant="int8" (ISSUE 13): each plane becomes an
        # (int8 values, f32 scales) tuple — values keep the page
        # layout, scales live in a per-slot-per-kv-head sidecar
        # [num_blocks, kv_heads, block_size] whose kv-head dim shards
        # EXACTLY like the values' (kv_scale_sharding; the canonical
        # cache_k_scale spec), so tp adds zero collectives. All-zero
        # init matches the dense pools' zeros bit-for-bit (0 * 0 = 0).
        if kv_quant == "int8":
            def _plane():
                return (jnp.zeros((num_blocks, kv_heads, block_size,
                                   head_dim), jnp.int8),
                        jnp.zeros((num_blocks, kv_heads, block_size),
                                  jnp.float32))
        else:
            def _plane():
                return jnp.zeros((num_blocks, kv_heads, block_size,
                                  head_dim), dtype)
        self.k = [_plane() for _ in range(num_layers)]
        self.v = [_plane() for _ in range(num_layers)]
        if kv_sharding is not None:
            import jax
            if kv_quant == "int8":
                if kv_scale_sharding is None:
                    raise ValueError(
                        "a sharded int8 pool needs kv_scale_sharding "
                        "(the sidecar scales must shard with their kv "
                        "heads, or every read pays an implicit gather)")

                def _put(plane):
                    return (jax.device_put(plane[0], kv_sharding),
                            jax.device_put(plane[1], kv_scale_sharding))
            else:
                def _put(plane):
                    return jax.device_put(plane, kv_sharding)
            self.k = [_put(a) for a in self.k]
            self.v = [_put(a) for a in self.v]
        self._free = list(range(num_blocks - 1, -1, -1))
        self._tables: dict = {}   # seq_id → [block ids]
        self._lens: dict = {}     # seq_id → context length
        self._ref: dict = {}      # block → ref count (present iff > 0)
        # prefix-cache index: chain hash ↔ physical block, plus the LRU
        # of cached-but-unreferenced blocks (insertion order = park
        # order; oldest evicted first when the free list runs dry)
        self._hash_of: dict = {}        # block → chain hash
        self._block_of: dict = {}       # chain hash → block
        self._lru: OrderedDict = OrderedDict()   # block → None
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        self.prefix_evictions = 0
        # optional fault-injection hook (utils/chaos.py): called at the
        # top of every _take_block, BEFORE any mutation, so an injected
        # KVCacheExhausted leaves the pool untouched
        self.fault_hook = None
        # optional telemetry tracer (utils/telemetry.py; ISSUE 12):
        # alloc/evict/splice/rollback land as flight-recorder events.
        # Attached by ServingEngine.set_telemetry; trace_pid is the
        # owning engine's replica id. None = zero-overhead no-op.
        self.tracer = None
        self.trace_pid = 0
        # optional LoRA adapter plane (ISSUE 10): a [num_blocks,
        # page_elems] f32 device array sharing THIS allocator's block
        # ids — a block either holds KV (rows of self.k/self.v) or an
        # adapter page (its row here); ownership is whatever the
        # ref-count says. None until enable_lora_pool.
        self.lora_pool = None
        self.lora_page_elems = 0

    # -- allocation ---------------------------------------------------------
    def _take_block(self) -> int:
        """Pop a writable block: the free list first, then (free list
        dry) evict the least-recently-parked cached block, invalidating
        its hash so it can never be spliced again."""
        if self.fault_hook is not None:
            self.fault_hook()
        if self._free:
            return self._free.pop()
        if self._lru:
            blk, _ = self._lru.popitem(last=False)
            h = self._hash_of.pop(blk)
            self._block_of.pop(h, None)
            self.prefix_evictions += 1
            if self.tracer is not None:
                self.tracer.event("kv_evict", pid=self.trace_pid,
                                  block=int(blk))
            return blk
        raise KVCacheExhausted("KV cache exhausted")

    def _take_blocks(self, n: int) -> List[int]:
        """Pop n blocks TRANSACTIONALLY: a mid-loop failure (free list
        drained between the capacity check and the take — only possible
        via an injected allocator fault) returns the already-taken
        blocks to the free list before re-raising, so no block is ever
        stranded outside the three pools."""
        taken: List[int] = []
        try:
            for _ in range(n):
                taken.append(self._take_block())
        except RuntimeError:
            self._free.extend(taken)
            raise
        return taken

    def allocate(self, seq_id: int, num_tokens: int):
        """Reserve blocks for a sequence of num_tokens (prefill)."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id} already allocated")
        needed = -(-num_tokens // self.block_size)
        if self.available_blocks < needed:
            raise KVCacheExhausted(
                f"KV cache exhausted: need {needed} blocks, "
                f"{self.available_blocks} free")
        blocks = self._take_blocks(needed)
        for b in blocks:
            self._ref[b] = 1
        self._tables[seq_id] = blocks
        self._lens[seq_id] = 0
        if self.tracer is not None:
            self.tracer.event("kv_alloc", pid=self.trace_pid,
                              seq=int(seq_id), blocks=int(needed),
                              dtype=self.pool_dtype)
        return self._tables[seq_id]

    # -- prefix caching ------------------------------------------------------
    def _chain_hashes(self, tokens, salt=None) -> List[int]:
        """Chain hash per FULL block of `tokens`:
        h_i = hash(h_{i-1}, tokens[i*bs:(i+1)*bs]); the chain makes a
        block's identity cover its whole prefix, so equal hashes mean
        equal content AND equal position history. ``salt`` seeds the
        chain root (multi-tenant serving passes the request's adapter
        id): equal prompts under different salts hash to disjoint
        chains, so prefix splices can never cross tenants — a block
        prefilled through adapter X holds X's K/V, which is junk to
        any other adapter's attention. salt=None (the default) keeps
        the original chain values bit-for-bit."""
        bs = self.block_size
        toks = [int(t) for t in tokens]
        out: List[int] = []
        h = None if salt is None else ("#tenant", salt)
        for i in range(len(toks) // bs):
            h = hash((h, tuple(toks[i * bs:(i + 1) * bs])))
            out.append(h)
        return out

    def _match(self, hashes: List[int],
               n_tokens: int) -> List[Tuple[int, int]]:
        matched: List[Tuple[int, int]] = []
        for h in hashes:
            blk = self._block_of.get(h)
            if blk is None:
                break
            matched.append((h, blk))
        if matched and len(matched) * self.block_size >= n_tokens:
            matched.pop()
        return matched

    def match_prefix(self, tokens, salt=None) -> List[Tuple[int, int]]:
        """Longest chain of already-cached full blocks covering a
        prefix of `tokens` — [(hash, block)], non-mutating. Capped so at
        least one token is left uncovered: the caller always prefills a
        non-empty suffix (the last position's logits must be computed).
        ``salt`` namespaces the chain (see _chain_hashes)."""
        return self._match(self._chain_hashes(tokens, salt),
                           len(tokens))

    def _prefix_capacity(self, matched, num_tokens: int):
        """(fresh blocks needed, blocks claimable) for an allocation
        splicing `matched`: matched blocks cost nothing (ref bump), and
        cached blocks not part of the match are evictable on demand."""
        needed = -(-num_tokens // self.block_size) - len(matched)
        evictable = len(self._lru) - sum(1 for _, b in matched
                                         if b in self._lru)
        return needed, len(self._free) + evictable

    def can_allocate_with_prefix(self, tokens, num_tokens: int,
                                 salt=None) -> bool:
        """Worst-case admission check that credits reusable blocks."""
        needed, avail = self._prefix_capacity(
            self.match_prefix(tokens, salt), num_tokens)
        return avail >= needed

    def allocate_with_prefix(self, seq_id: int, tokens,
                             num_tokens: Optional[int] = None,
                             salt=None):
        """Reserve blocks for a prompt of `tokens` (worst-case capacity
        `num_tokens` ≥ len(tokens)), splicing in every cached block of
        the longest matching block-aligned prefix (ref++, no copy).
        Returns (reused_blocks, n_cached_tokens); the sequence's context
        length starts at n_cached_tokens, so `extend` hands out slots
        for the uncovered suffix only. The suffix's own full prompt
        blocks are registered in the hash index immediately — their
        content is fully determined by the prompt, so later requests may
        splice them as soon as the owning prefill has been dispatched
        (dispatch ordering is the caller's job; see ServingEngine's
        admission waves)."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id} already allocated")
        n_tok = len(tokens) if num_tokens is None else int(num_tokens)
        hashes = self._chain_hashes(tokens, salt)
        matched = self._match(hashes, len(tokens))
        needed_new, avail = self._prefix_capacity(matched, n_tok)
        if avail < needed_new:
            raise KVCacheExhausted(
                f"KV cache exhausted: need {needed_new} blocks, "
                f"{avail} free")
        reused = []
        for _, blk in matched:          # revive/ref BEFORE taking fresh
            self._lru.pop(blk, None)    # blocks so eviction can't steal
            self._ref[blk] = self._ref.get(blk, 0) + 1   # a matched one
            reused.append(blk)
        try:
            fresh = self._take_blocks(needed_new)
        except RuntimeError:
            # injected fault mid-take: undo the revive so the matched
            # blocks return to ref-0 parked state and the pool invariant
            # holds (the refusal must leave the pool unchanged)
            for blk in reused:
                self._ref[blk] -= 1
                if self._ref[blk] == 0:
                    del self._ref[blk]
                    self._lru[blk] = None
            raise
        for b in fresh:
            self._ref[b] = 1
        table = reused + fresh
        self._tables[seq_id] = table
        n_cached = len(reused) * self.block_size
        self._lens[seq_id] = n_cached
        self.prefix_query_tokens += len(tokens)
        self.prefix_hit_tokens += n_cached
        if self.tracer is not None:
            self.tracer.event("kv_alloc", pid=self.trace_pid,
                              seq=int(seq_id), blocks=int(needed_new),
                              spliced=len(reused),
                              dtype=self.pool_dtype)
            if reused:
                self.tracer.event(
                    "kv_splice", pid=self.trace_pid, seq=int(seq_id),
                    blocks=len(reused), tokens=int(n_cached))
        # register the suffix's full prompt blocks for future reuse
        for i in range(len(reused), len(hashes)):
            h, b = hashes[i], table[i]
            if h not in self._block_of and b not in self._hash_of:
                self._block_of[h] = b
                self._hash_of[b] = h
        return reused, n_cached

    def clear_prefix_cache(self):
        """Drop every cached (unreferenced) block back to the free list
        and forget all hashes — e.g. between warmup phases so throwaway
        traffic cannot splice into real requests' programs."""
        for blk in self._lru:
            self._free.append(blk)
        self._lru.clear()
        self._hash_of.clear()
        self._block_of.clear()

    def reset_prefix_stats(self):
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        self.prefix_evictions = 0

    def unregister_block_hashes(self, blocks):
        """Invalidate the hash registrations of `blocks` — used when a
        prefill is unwound (cancel / failure / preemption) before the
        dispatch covering those blocks was issued: their registered
        content will never be written, so they must not be spliceable.
        Only registrations actually pointing at the block are removed
        (another request may have re-registered the same hash onto a
        different block). No-op for unhashed blocks."""
        for b in blocks:
            h = self._hash_of.get(b)
            if h is not None and self._block_of.get(h) == b:
                del self._hash_of[b]
                del self._block_of[h]
                if b in self._lru:
                    # a parked block losing its hash is no longer
                    # spliceable — return it to the free list (cached
                    # blocks must all be hash-registered)
                    del self._lru[b]
                    self._free.append(b)

    # -- LoRA adapter paging (ISSUE 10; see inference/lora.py) --------------
    def enable_lora_pool(self, page_elems: int, sharding=None):
        """Attach the adapter-page plane: [num_blocks, page_elems]
        f32, zero-initialized (the scratch block's row stays zero
        forever — it IS the null adapter every base-only row reads).
        ``sharding`` replicates the plane over a tp mesh. Idempotent
        for a matching page size; a mismatch raises (two registries
        with different layouts cannot share one pool)."""
        if self.lora_pool is not None:
            if self.lora_page_elems != int(page_elems):
                raise ValueError(
                    f"lora pool already enabled with page_elems="
                    f"{self.lora_page_elems}, got {page_elems}")
            return
        self.lora_page_elems = int(page_elems)
        pool = jnp.zeros((self.num_blocks, self.lora_page_elems),
                         jnp.float32)
        if sharding is not None:
            import jax
            pool = jax.device_put(pool, sharding)
        self.lora_pool = pool

    def write_lora_pages(self, blocks: List[int], pages):
        """Upload host page data ([n, page_elems]) into the plane rows
        of ``blocks`` — the adapter fault-in path. Functional scatter:
        the plane is never donated, so a retried upload is safe."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        self.lora_pool = self.lora_pool.at[idx].set(
            jnp.asarray(np.asarray(pages, np.float32)))

    def lookup_hash(self, h) -> Optional[int]:
        """The block currently registered under chain hash ``h`` (KV
        prefix or synthetic adapter-page hash), else None."""
        return self._block_of.get(h)

    def register_page_hashes(self, blocks: List[int], hashes):
        """Register synthetic hashes onto referenced blocks (adapter
        fault-in): when the owning pseudo-sequence later frees, the
        pages PARK in the cached-LRU instead of dropping to the free
        list — resident-but-cold, revivable via adopt_cached_blocks,
        evictable by anyone. Skips hashes/blocks already taken (same
        contract as the prompt-suffix registration path)."""
        for b, h in zip(blocks, hashes):
            if h not in self._block_of and b not in self._hash_of:
                self._block_of[h] = b
                self._hash_of[b] = h

    def adopt_cached_blocks(self, seq_id: int, blocks: List[int]):
        """Claim PARKED (cached, ref-0) blocks as ``seq_id``'s table —
        the adapter-revival fast path (a cold adapter's pages come
        straight back out of the LRU; no upload, no allocation).
        All-or-nothing: every block must currently be parked."""
        if seq_id in self._tables:
            raise ValueError(f"seq {seq_id} already allocated")
        for b in blocks:
            if b not in self._lru:
                raise KeyError(f"block {b} is not parked in the "
                               f"cached-LRU")
        for b in blocks:
            del self._lru[b]
            self._ref[b] = 1
        self._tables[seq_id] = list(blocks)
        self._lens[seq_id] = 0
        return self._tables[seq_id]

    def extend(self, seq_id: int):
        """Ensure room for one more token; returns the flat slot id."""
        pos = self._lens[seq_id]
        blocks = self._tables[seq_id]
        if pos >= len(blocks) * self.block_size:
            if self.available_blocks == 0:
                raise KVCacheExhausted("KV cache exhausted on extend")
            blk = self._take_block()
            self._ref[blk] = 1
            blocks.append(blk)
        self._lens[seq_id] = pos + 1
        block = blocks[pos // self.block_size]
        return block * self.block_size + pos % self.block_size

    def rollback(self, seq_id: int, new_len: int,
                 min_blocks: int = 0):
        """Roll a live sequence's context length BACK to ``new_len`` —
        the speculative-decoding unwind: slots handed out (via extend)
        for draft tokens past the accepted prefix are rescinded, so the
        next extend re-issues them and overwrites the rejected tail's
        K/V. Slots between new_len and the old length are masked by
        every reader until then (attention visibility is bounded by
        context length), so the junk they hold is unreachable.

        Blocks now WHOLLY past the new length leave the table (ref--;
        at ref 0 they return straight to the free list, never the
        cached-LRU — their content was never valid) and any hash
        registration pointing at them is invalidated (a block that held
        rejected drafts must not be spliceable). ``min_blocks`` FLOORS
        the truncation: the caller passes the table length from before
        its speculative extends, so only blocks those extends appended
        are ever dropped — an up-front worst-case admission
        reservation (whose tail the sequence has not reached yet) must
        survive every rollback, or the "a running request can never
        exhaust the pool" guarantee silently dies. Shared (ref > 1)
        blocks cannot appear in the dropped tail in practice — splices
        cover prompt prefixes, and speculative slots are past the whole
        emitted history — but the ref discipline handles them anyway.
        """
        blocks = self._tables[seq_id]
        cur = self._lens[seq_id]
        new_len = int(new_len)
        if not 0 <= new_len <= cur:
            raise ValueError(
                f"rollback(seq {seq_id}) to {new_len} outside "
                f"[0, {cur}]")
        keep = max(1, -(-new_len // self.block_size), int(min_blocks))
        dropped = blocks[keep:]
        del blocks[keep:]
        self._lens[seq_id] = new_len
        returned = []
        for b in reversed(dropped):
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                h = self._hash_of.pop(b, None)
                if h is not None:
                    self._block_of.pop(h, None)
                returned.append(b)
        self._free.extend(returned)
        if self.tracer is not None:
            self.tracer.event(
                "kv_rollback", pid=self.trace_pid, seq=int(seq_id),
                new_len=new_len, dropped=len(dropped))

    def free(self, seq_id: int):
        """Release a sequence: ref-- on each of its blocks; blocks
        reaching ref 0 are parked in the cached-LRU when they carry a
        valid hash (contents stay reusable) or returned to the free
        list otherwise. A no-op for unknown / already-freed seq_ids —
        a double free must not decrement someone else's refs."""
        blocks = self._tables.pop(seq_id, None)
        self._lens.pop(seq_id, None)
        if blocks is None:
            return
        returned = []
        # park LEAF-first: eviction pops oldest-parked, and a chain dies
        # from its head — parking the head last keeps the hot prefix
        # matchable longest (evicting a head orphans every descendant)
        for b in reversed(blocks):
            self._ref[b] -= 1
            if self._ref[b] == 0:
                del self._ref[b]
                if b in self._hash_of:
                    self._lru[b] = None      # park: newest at the end
                else:
                    returned.append(b)
        self._free.extend(returned)

    def context_len(self, seq_id: int) -> int:
        return self._lens.get(seq_id, 0)

    def block_table(self, seq_id: int, max_blocks: int) -> np.ndarray:
        t = self._tables[seq_id]
        out = np.zeros(max_blocks, np.int32)
        out[:len(t)] = t
        return out

    def seq_blocks(self, seq_id: int) -> List[int]:
        """The sequence's physical block list (read-only view)."""
        return list(self._tables[seq_id])

    # -- pool-footprint introspection (ISSUE 13) ----------------------------
    @property
    def pool_dtype(self) -> str:
        """The pool's storage dtype as stats()/telemetry report it:
        'int8' for the quantized layout, else the plane dtype name."""
        if self.kv_quant == "int8":
            return "int8"
        return str(np.dtype(_plane_values(self.k[0]).dtype))

    def pool_bytes(self) -> int:
        """Total device bytes of the K/V planes (sidecar scales
        included) — the logical (global, unsharded) footprint."""
        total = 0
        for plane in list(self.k) + list(self.v):
            leaves = plane if isinstance(plane, tuple) else (plane,)
            for a in leaves:
                total += int(np.prod(a.shape, dtype=np.int64)
                             * np.dtype(a.dtype).itemsize)
        return total

    def bytes_per_token(self) -> float:
        """KV bytes one token slot costs across all layers (k + v,
        scales included) — pool_bytes over the pool's slot count; the
        capacity headline kv_quant halves."""
        return self.pool_bytes() / float(self.num_blocks
                                         * self.block_size)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def cached_blocks(self) -> int:
        """Blocks parked in the prefix-cache LRU (reusable, evictable)."""
        return len(self._lru)

    @property
    def available_blocks(self) -> int:
        """Blocks a fresh allocation can claim: free + evictable."""
        return len(self._free) + len(self._lru)

    def debug_check(self):
        """Pool invariant: free + cached + referenced == num_blocks,
        the three sets disjoint, table refs exactly matching the ref
        counts (no leak, no double free), the hash index a bijection
        with every cached block hash-registered, and every live
        sequence's context length inside its table's capacity —
        PARTIALLY-PREFILLED sequences included (a chunked prefill
        extends its length over several scheduler steps; between any
        two chunks the length must sit within the blocks reserved at
        admission and never go negative). Raises AssertionError on
        violation; cheap enough to run after every scheduler step in
        tests."""
        free = set(self._free)
        cached = set(self._lru)
        referenced = set(self._ref)
        assert len(free) == len(self._free), "duplicate free blocks"
        assert not free & cached and not free & referenced \
            and not cached & referenced, "block in two pools at once"
        assert len(free) + len(cached) + len(referenced) \
            == self.num_blocks, (
                f"pool leak: free={len(free)} cached={len(cached)} "
                f"referenced={len(referenced)} != {self.num_blocks}")
        counts = Counter()
        for t in self._tables.values():
            counts.update(t)
        assert dict(counts) == self._ref, "ref counts out of sync"
        assert all(self._block_of.get(h) == b
                   for b, h in self._hash_of.items()) \
            and len(self._block_of) == len(self._hash_of), \
            "hash index not a bijection"
        assert all(b in self._hash_of for b in cached), \
            "cached block without a hash"
        # per-sequence consistency, incl. partially-prefilled sequences
        assert set(self._lens) == set(self._tables), \
            "length/table bookkeeping out of sync"
        for s, t in self._tables.items():
            ln = self._lens[s]
            assert t and 0 <= ln <= len(t) * self.block_size, (
                f"seq {s}: context length {ln} outside its "
                f"{len(t)}-block table (partial-prefill bound)")
            assert all(0 <= b < self.num_blocks for b in t), \
                f"seq {s}: block id out of range"

    # -- device updates -----------------------------------------------------
    def write(self, layer: int, k, v, slot_mapping):
        """Write one step's K/V for `layer` at the given flat slots."""
        nk, nv = reshape_and_cache(k, v, self.k[layer], self.v[layer],
                                   slot_mapping)
        self.k[layer] = nk
        self.v[layer] = nv


def paged_attention_impl(head_dim: int, block_size: int,
                         quantized: bool) -> str:
    """Which implementation the paged-attention entry points take for
    a pool of this geometry: "pallas", or "reference (<why>)". The
    kernel is admitted only where the chip's compiler accepts it: the
    v5e's Mosaic refuses the HBM page slice [1, kvh, bs, 64] of a
    head_dim-64 pool and the [1, kvh, bs] scale-sidecar slice of an
    int8 pool ("must be aligned to tiling (128)" — ROADMAP S3), so
    those two serve through the jnp reference until the kernels are
    repaired. The serving engine logs this string per program family
    at construction."""
    from .pallas import interpret
    if interpret():
        return f"reference (backend {jax.default_backend()})"
    from ..utils.flags import FLAGS
    if not getattr(FLAGS, "use_pallas_kernels", True):
        return "reference (FLAGS.use_pallas_kernels off)"
    if quantized:
        return "reference (int8 KV pool: kernel refused by Mosaic)"
    if head_dim % 128:
        return (f"reference (head_dim {head_dim} not a multiple of "
                f"128: kernel refused by Mosaic)")
    if block_size % 8:
        return f"reference (block_size {block_size} not a multiple of 8)"
    return "pallas"


def _pallas_decode_ok(q, k_cache):
    # layout [num_blocks, kv_heads, block_size, d] (tuple-aware)
    return paged_attention_impl(
        q.shape[-1], _plane_values(k_cache).shape[2],
        isinstance(k_cache, tuple)) == "pallas"


def paged_attention_decode(q, k_cache, v_cache, block_tables, context_lens,
                           scale: Optional[float] = None):
    """One-token decode attention over the paged cache; Pallas
    scalar-prefetch kernel where paged_attention_impl admits it, jnp
    reference elsewhere. See paged_attention_decode_reference for the
    signature."""
    if _pallas_decode_ok(q, k_cache):
        from .pallas.paged_attention import paged_attention_decode_pallas
        return paged_attention_decode_pallas(q, k_cache, v_cache,
                                             block_tables, context_lens,
                                             scale)
    return paged_attention_decode_reference(q, k_cache, v_cache,
                                            block_tables, context_lens,
                                            scale)
