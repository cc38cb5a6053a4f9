"""Continuous-batching LLM serving engine over the paged KV pool.

Reference: the AnalysisPredictor serving subsystem
(/root/reference/paddle/fluid/inference/api/analysis_predictor.h:100)
plus the block_multihead_attention continuous-decode path
(/root/reference/python/paddle/incubate/nn/functional/
block_multihead_attention.py). The reference composes CUDA kernels under
a pass-optimized executor; the TPU-native equivalent is a *fixed-shape*
scheduler: XLA programs cannot change batch size per step, so continuous
batching becomes a fixed grid of batch slots with per-slot activity —
the same trick the paged pool already plays for sequence length.

Architecture (all shapes static; compiled programs: ONE decode chunk
per ladder rung, TWO prefill widths per active prompt bucket, plus two
width-1 no-sample chunk programs when chunked prefill is on):
- admission: queued requests claim free batch slots (capacity-aware,
  FIFO) and enter the "prefilling" state. Admission only allocates —
  it never dispatches or blocks on the device.
- chunked prefill (Sarathi-style; prefill_chunk=256 by default): a
  prompt suffix longer than one chunk is split into fixed-size chunks;
  chunk i prefills at position offset i*C with chunks 0..i-1's pages
  riding along as a prefix table — exactly the prefix-cache-hit
  machinery, so one compiled (C, width-1) program serves every chunk
  of every prompt. Intermediate chunks sample nothing (no last-token
  logits; the no-sample programs consume no PRNG key); only the FINAL
  chunk takes the first-token logits. The scheduler interleaves
  prefill chunks with decode chunks under a per-step token budget
  (prefill_budget, default one chunk), so a long prompt arriving
  mid-stream delays running decodes by at most ~one chunk of prefill
  per decode chunk instead of the whole prompt — the ITL cliff the
  monolithic path had. Prefill dispatches join the SAME in-flight
  queue as decode chunks; their results are fetched at collection
  time, never inside admission.
- automatic prefix caching (prefix_caching=True, the default): on
  admission the prompt is hashed at block granularity against the
  pool's chain-hash index (PagedKVCache.match_prefix); matched full
  blocks are spliced into the request's block table (ref++, no copy)
  and ONLY the uncovered suffix prefills — bucketed on SUFFIX length,
  RoPE positions and slot mappings offset by n_cached, attention run
  over [gathered prefix pages ++ suffix] (the decoder's
  _prefill_prefix_impl; n_cached is data, so one compiled program per
  (bucket, width) serves every hit length). The worst-case admission
  capacity check credits reusable blocks, so cache hits raise
  effective pool capacity. A request may splice blocks that another
  still-prefilling request has yet to write (they register in the
  hash index at allocation): the reader records a dependency on the
  writer's dispatch progress and its own chunks hold back until the
  writer's covering chunk has been dispatched — device program order
  then makes the write visible to the read. Retired requests return
  blocks through the ref-counted path: full hashed blocks park in the
  pool's LRU for future splices and are evicted only when the free
  list runs dry.
- decode: ONE program serves every step — a lax.scan over a
  chunk_size-token schedule (the page/slot schedule is deterministic, so
  the host precomputes it), [max_batch] wide, inactive / finished /
  still-prefilling slots aimed at the scratch page and their outputs
  discarded. Sampling (per-slot temperature, engine-static top_k)
  happens in-program, so only [max_batch, chunk] token ids cross the
  host boundary per chunk. Chunking is what makes continuous batching
  viable on TPU: the per-dispatch host cost amortizes over chunk_size
  tokens, while admission still happens every chunk boundary.
- completion: EOS/max-token slots free their pages (mid-chunk EOS trims
  the tail tokens); the slot admits the next queued request at the next
  chunk boundary.

Weight-only int8 (weight_dtype="int8") stores matmul weights as
per-channel int8 + scale — decode is HBM-bandwidth-bound, so halving
weight bytes is the serving-side quantization that actually pays on TPU.

Fault tolerance (ISSUE 4 — the runtime analogue of flightcheck):
failures are absorbed at REQUEST granularity; step() never raises on a
per-request fault and the pool invariant holds through every recovery.
- deadlines/cancel: SamplingParams.deadline_s + cancel(req_id) move a
  request to a terminal ABORTED state from any live stage, unwinding
  splice-pending hash registrations, restarting dependent readers and
  freeing pages only once no in-flight chunk references them.
- preemption-with-recompute: admission="optimistic" oversubscribes the
  pool (prefill pages only); KV pressure preempts the newest/lowest-
  priority running request, whose generated history re-prefills through
  the NO-SAMPLE chunk programs (no PRNG key drawn — the engine key
  stream is untouched, so greedy outputs are token-identical) riding
  the prefix cache for near-zero recompute on hits. Epoch guards drop a
  preempted life's in-flight tokens at collection.
- bounded retry: every dispatch/fetch goes through _device_call —
  exponential-backoff retries re-issue the SAME call (same key), then
  fail the involved requests with a structured Request.error.
- overload shedding: add_request raises EngineOverloaded on the queue
  cap or when backlog/rate math says a deadline cannot be met.
- chaos: utils/chaos.ChaosMonkey injects seeded allocator OOMs,
  dispatch/collect faults and latency spikes at the sanctioned hooks;
  tools/chaos_serving.py gates token-identity under fault schedules.

Speculative decoding (ISSUE 9; spec_decode=SpecConfig(...)): a host
drafter (n-gram/prompt-lookup by default; any Drafter plugs in)
proposes k continuation tokens per greedy decode column, which ride as
EXTRA ROWS of the ragged program — carried token at position ctx,
drafts at ctx+1..ctx+k, each with row_ctx = position + 1, the exact
visibility contract prefill-chunk rows already use. One forward gives
the teacher's token at every position; the decoder's _spec_accept
computes the longest-accepted-prefix IN-program and neutralizes
rejected rows' pool writes via the scratch slot; the host delivers
1..k+1 tokens per column per dispatch and rolls the allocator back
past them (PagedKVCache.rollback). Greedy outputs are bit-identical to
spec-off — every emitted token is the teacher's own argmax under a
verified prefix. Verify chunks are synchronous (acceptance decides the
next schedule); draft rows compete with prefill chunks under the
per-step row budget; rich-sampling columns pause drafting. All PR-4
invariants hold with drafts in flight: a mid-window preemption blanks
the victim's rows through the staleness sweep, epoch guards drop a
previous life's verify results, and dispatch/collect retries re-issue
the same program.

Fleet serving (ISSUE 11): ONE engine is ONE failure domain. R engines
compose into a dp x tp fleet behind inference/fleet.py::Router —
prefix-affinity routing over each replica's chain-hash index, a
per-replica circuit breaker fed by this engine's dispatch_exhaustions
counter, and drain-and-migrate failover riding adopt_request (the
preemption-recompute machinery pointed across engines: history
re-prefills through the no-sample chunk programs, so greedy outputs
are token-identical across the migration). The engine itself stays
fleet-agnostic; devices= is the only constructor surface the Router
needs (a disjoint device slice per tp-sharded replica).
"""
from __future__ import annotations

import functools
import itertools
import logging
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..ops.paged_attention import KVCacheExhausted, paged_attention_impl
from ..utils import telemetry
from ..utils.telemetry import (CompileWatch, Reservoir, SLOMonitor,
                               SLOPolicy)
from .paged_decode import PagedLlamaDecoder
from .spec_decode import SpecConfig

_log = logging.getLogger("paddle_tpu.serving")

__all__ = ["EngineOverloaded", "SamplingParams", "Request",
           "ServingEngine", "SpecConfig"]


# the stats() float each timed phase of an engine step feeds; a phase
# that is not here (engine.step, .deadlines, .admit, .deliver) is a span
# only, so the three floats keep the meaning they had before the phases
# had names
_PHASE_FLOAT = {
    "engine.plan": "time_host_s",
    "engine.dispatch": "time_host_s",
    "engine.collect": "time_stall_s",
    "engine.prefill_dispatch": "time_prefill_s",
    "engine.prefill_collect": "time_prefill_s",
}


class _Phase:
    """One named phase of an engine step: always adds its wall seconds
    to the float ``_PHASE_FLOAT`` names and to ``time_by_phase_s``; a
    ``telemetry.span`` under it records while someone listens. A
    context manager, because the phases return early."""

    __slots__ = ("eng", "name", "span", "t0")

    def __init__(self, eng, name, attrs):
        self.eng, self.name = eng, name
        self.span = telemetry.span(name, tracer=eng.tracer,
                                   pid=eng.replica_id, **attrs)

    def set(self, **attrs):
        self.span.set(**attrs)

    def __enter__(self):
        self.span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        field_ = _PHASE_FLOAT.get(self.name)
        if field_ is not None:
            dt = time.perf_counter() - self.t0
            eng = self.eng
            setattr(eng, field_, getattr(eng, field_) + dt)
            eng.time_by_phase_s[self.name] = \
                eng.time_by_phase_s.get(self.name, 0.0) + dt
        return self.span.__exit__(*exc)


def _phased(name):
    """Run a ServingEngine method as the phase ``name``; the body reaches
    it as ``self._ph`` (``self._ph.set(T=8)``)."""
    def deco(fn):
        @functools.wraps(fn)
        def run(self, *args, **kw):
            outer = self._ph
            with self._phase(name) as self._ph:
                try:
                    return fn(self, *args, **kw)
                finally:
                    self._ph = outer
        return run
    return deco


class EngineOverloaded(RuntimeError):
    """Typed admission rejection (overload shedding): the queue-depth x
    deadline estimate says the request cannot meet its deadline, or the
    hard queue-depth cap is hit. Raised by add_request BEFORE the
    request is queued, so the caller can retry elsewhere / later —
    rejecting at admission is cheaper than burning pool capacity on a
    request that will be dead on arrival."""


class _DispatchFailed(Exception):
    """Internal: a device dispatch/fetch exhausted its retry budget.
    Carries the site kind and the last underlying exception; converted
    by the call site into structured per-request failures (the engine
    itself never dies on a dispatch error)."""

    def __init__(self, kind: str, cause: BaseException):
        super().__init__(f"{kind}: {cause!r}")
        self.kind = kind
        self.cause = cause


@dataclass
class SamplingParams:
    """Per-request sampling controls (reference generation surface:
    /root/reference/python/paddle/nn/decode.py:994 dynamic_decode +
    the incubate serving path). temperature<=0 means greedy; top_k=None
    defers to the engine-level top_k default while top_k=0 explicitly
    disables the filter (even against an engine default); top_p=1.0
    and repetition_penalty=1.0 are off. All are PER REQUEST and applied
    in-program (mask-based — no new compile variants per value)."""
    temperature: float = 0.0
    max_new_tokens: int = 32
    eos_token_id: Optional[int] = None
    top_k: Optional[int] = None
    top_p: float = 1.0
    repetition_penalty: float = 1.0
    # -- fault-tolerance surface ------------------------------------------
    # deadline_s: wall-clock budget from submit; a request past it is
    # ABORTED (partial tokens kept, deadline_misses counted) and — when
    # the engine can already tell at admission that the deadline cannot
    # be met — shed with EngineOverloaded instead of queued.
    deadline_s: Optional[float] = None
    # priority: higher survives longer under KV pressure (preemption
    # victims are picked lowest-priority-first, newest-first on ties)
    priority: int = 0
    # -- multi-tenant surface (ISSUE 10) ----------------------------------
    # adapter_id: serve this request through a LoRA adapter registered
    # in the engine's AdapterRegistry (None = the base model). The
    # adapter is faulted into the shared block pool at admission and
    # its per-row deltas ride the ragged step program; prefix-cache
    # hashes are salted with the id so splices never cross tenants.
    adapter_id: Optional[object] = None
    # allowed_tokens: vocab restriction applied IN-PROGRAM to this
    # request's decode columns before sampling (the minimal structured
    # decoding hook — "own output schema"; grammar FSMs are future
    # work). Either a boolean mask of length vocab_size or a sequence
    # of allowed token ids; greedy becomes constrained greedy (argmax
    # over the masked logits) and sampling renormalizes over the mask.
    allowed_tokens: Optional[object] = None

    @property
    def needs_rich_sampling(self) -> bool:
        # an EXPLICIT top_k (including 0, which must be able to override
        # an engine-level default) routes through the per-request path;
        # a vocab mask rides the same mask-based program family
        return (self.top_k is not None or self.top_p < 1.0
                or self.repetition_penalty != 1.0
                or self.allowed_tokens is not None)


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray                    # [prompt_len] int32
    sampling: SamplingParams
    out_tokens: List[int] = field(default_factory=list)
    t_submit: float = 0.0
    t_admit: Optional[float] = None       # slot claimed (queue wait ends)
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None
    # queued | prefilling | running | done, plus the terminal fault
    # states: aborted (cancel/deadline — partial tokens kept) and
    # failed (dispatch error after retries — structured `error` set)
    state: str = "queued"
    error: Optional[str] = None   # why the request aborted/failed
    # tokens DISPATCHED (prefill + scheduled decode steps) — may exceed
    # len(out_tokens) while a chunk is in flight or after an EOS cut
    planned: int = 0
    # -- preemption-with-recompute ----------------------------------------
    # resume: the request was preempted while RUNNING; on re-admission
    # its prefill source is prompt ++ out_tokens[:-1] (the generated
    # history re-enters the pool via no-sample chunks — no PRNG key is
    # consumed, so the engine's key stream is untouched) and decode
    # resumes from out_tokens[-1] without re-sampling anything.
    resume: bool = False
    # ctx: the token array the CURRENT allocation's prefill reads
    # (prompt for a fresh admission, prompt ++ out_tokens[:-1] for a
    # resume) — set by _admit, None while queued
    ctx: Optional[np.ndarray] = None
    # epoch: bumped every time the request loses its slot (preemption,
    # restart); in-flight chunks record the epoch they were scheduled
    # against so collection can drop results from a previous life
    epoch: int = 0
    # -- chunked-prefill progress (valid from admission) ------------------
    n_cached: int = 0             # prompt tokens spliced from the cache
    prefill_sent: int = 0         # suffix tokens DISPATCHED so far
    # splice-pending dependencies: (writer request, suffix tokens the
    # writer must have dispatched before our first chunk may read its
    # pages) — see ServingEngine._admit
    deps: List[Tuple["Request", int]] = field(default_factory=list)
    pending_blocks: List[int] = field(default_factory=list)
    slot: Optional[int] = None
    # -- multi-tenant bookkeeping (ISSUE 10) ------------------------------
    # lora_held: this request currently holds one acquire() on its
    # adapter (set at admission, dropped whenever the slot is lost)
    lora_held: bool = False
    # allowed_mask: sampling.allowed_tokens normalized to a [vocab]
    # bool mask at add_request (None = unrestricted)
    allowed_mask: Optional[np.ndarray] = None
    # inter-token latency samples (seconds/token, chunk time split
    # evenly over the chunk's delivered tokens — see _collect_oldest)
    itls: List[float] = field(default_factory=list)
    t_last_emit: Optional[float] = None
    # -- telemetry (ISSUE 12; all None/0 while tracing is off) ------------
    # trace_id: the request's lifetime async-span id on the engine's
    # Tracer — stable across preemption lives AND cross-replica
    # migration (adopt_request continues it), so the whole lifecycle
    # renders as ONE span in Perfetto
    trace_id: Optional[int] = None
    t_queued: float = 0.0         # current queued-life start
    t_life: float = 0.0           # current life's slot-admission time
    t_run: Optional[float] = None   # current life's running transition
    t_wait: Optional[float] = None  # splice-wait start (deps unmet)
    # trace_keep_open: the fleet Router sets this before its drain
    # cancels a request it is about to MIGRATE — the local abort must
    # not close the lifetime span (the adopted continuation on the new
    # replica ends it), or the migrated request would render as two
    # disjoint spans instead of one continuous one
    trace_keep_open: bool = False

    @property
    def prefill_tokens(self) -> np.ndarray:
        """The token array the current prefill reads: the prompt, or
        prompt ++ generated history for a preemption resume."""
        return self.ctx if self.ctx is not None else self.prompt

    @property
    def suffix_len(self) -> int:
        """Prefill tokens that must actually run (past the splice)."""
        return int(len(self.prefill_tokens)) - self.n_cached

    @property
    def deadline_at(self) -> Optional[float]:
        if self.sampling.deadline_s is None:
            return None
        return self.t_submit + self.sampling.deadline_s

    @property
    def ttft_s(self) -> Optional[float]:
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    @property
    def queue_wait_s(self) -> Optional[float]:
        if self.t_admit is None:
            return None
        return self.t_admit - self.t_submit

    @property
    def latency_s(self) -> Optional[float]:
        if self.t_done is None:
            return None
        return self.t_done - self.t_submit


def _normalize_prompt(prompt) -> np.ndarray:
    """Prompt intake shared by engine admission and the fleet Router:
    Tensor unwrap, int32 flatten, empty rejection — ONE definition so
    the two surfaces cannot drift."""
    if isinstance(prompt, Tensor):
        prompt = np.asarray(prompt._value)
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    if prompt.size == 0:
        raise ValueError("empty prompt")
    return prompt


def _bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(
        f"prompt length {n} exceeds the largest prefill bucket: "
        f"configured prompt_buckets={tuple(buckets)} top out at "
        f"{buckets[-1]} tokens; raise prompt_buckets (or shorten the "
        f"prompt). Oversized prompts are rejected at add_request time "
        f"so they never reach dispatch.")


class ServingEngine:
    """Mixed-length concurrent request serving for a LlamaForCausalLM.

    Usage:
        eng = ServingEngine(model, max_batch_size=8)
        rid = eng.add_request(prompt_ids, SamplingParams(max_new_tokens=64))
        while eng.step():
            pass
        tokens = eng.result(rid)
    """

    def __init__(self, model, max_batch_size: int = 8,
                 num_blocks: int = 512, block_size: int = 16,
                 prompt_buckets: Sequence[int] = (32, 64, 128, 256, 512),
                 weight_dtype: Optional[str] = None, top_k: int = 0,
                 chunk_size: int = 8, seed: int = 0,
                 overlap: bool = True, mesh=None,
                 chunk_schedule: Optional[Sequence[int]] = None,
                 prefix_caching: bool = True,
                 prefill_chunk: Optional[int] = 256,
                 prefill_budget: Optional[int] = None,
                 max_dispatch_retries: int = 2,
                 retry_backoff_s: float = 0.05,
                 admission: str = "worst_case",
                 max_queue_depth: Optional[int] = None,
                 ragged: bool = False, tp: int = 1,
                 tp_comm: Optional[str] = None,
                 devices: Optional[Sequence] = None,
                 spec_decode: Optional[SpecConfig] = None,
                 lora=None, tracer=None,
                 kv_quant: Optional[str] = None,
                 slo=None,
                 profile_every: Optional[int] = None,
                 profile_seed: int = 0,
                 ragged_idle_cap: Optional[int] = None,
                 multi_step: int = 1):
        from .gpt_decode import PagedGPTDecoder
        # -- multi-chip tensor-parallel serving (ROADMAP 1) -----------------
        # tp=N builds a one-axis "tp" mesh over the first N devices and
        # runs the WHOLE serving step — the ragged [T, W] program,
        # in-program sampling, paged KV append — fully-manual under
        # shard_map: decoder weights placed by the canonical SpecLayout
        # table (wq/wk/wv/wg/wu/head column-parallel, wo/wd
        # row-parallel, embed/norms replicated), the KV pool sharded
        # over the kv-head dim (each shard appends exactly the heads it
        # computed — zero collectives on the append path), exactly ONE
        # allreduce per attention/MLP block plus one all-gather over
        # the per-shard vocab logits before sampling. tp_comm="int8"
        # swaps the block allreduces for the EQuARX-style quantized
        # collective (distributed.collective.int8_all_reduce); the
        # logits gather stays exact. tp>1 forces ragged=True — one
        # sharded program per step IS the multi-chip serving step.
        # tp_comm=None (the default) means "the decoder's mode" —
        # fp32 when the engine builds the decoder itself; an EXPLICIT
        # value that contradicts a prebuilt decoder raises (the comm
        # mode is baked into the decoder's compiled programs, and a
        # silently-substituted mode corrupts exactly the fp32-vs-int8
        # A/B the flag exists for).
        tp = int(tp)
        if tp < 1:
            raise ValueError(f"tp must be >= 1, got {tp}")
        if tp_comm not in (None, "fp32", "int8"):
            raise ValueError(f"tp_comm must be 'fp32' or 'int8', got "
                             f"{tp_comm!r}")
        # -- multi-step fused decode (ISSUE 16) -----------------------------
        # multi_step=k fuses k consecutive pure-decode serving steps
        # into ONE device program: a lax.scan over k*T ragged decode
        # ministeps with in-program KV append, in-program sampling
        # carried across iterations, and on-device EOS bookkeeping (a
        # per-column live mask freezes finished columns to the scratch
        # slot, so late iterations are no-ops for them). The host
        # collects k*T tokens per column per dispatch, amortizing the
        # host-schedule + dispatch-queue floor the observatory
        # measures. Scheduler invariants (admission, deadlines, epoch
        # guards, preemption, debug_check) move to k-step boundaries:
        # step() dispatches one whole window, so a mid-window cancel
        # or deadline takes effect at the NEXT boundary. Fused windows
        # only dispatch in the pure-decode regime — any prefilling
        # slot drops the engine back to single-step chunks until the
        # prefill drains, so chunked-prefill/splice semantics are
        # untouched. Greedy outputs are token-identical to
        # multi_step=1 (greedy sampling depends only on context, and
        # a window never writes KV a single-step schedule would not).
        multi_step = int(multi_step)
        if multi_step < 1:
            raise ValueError(f"multi_step must be >= 1, got "
                             f"{multi_step}")
        if multi_step > 1 and spec_decode is not None:
            # both features re-schedule the decode token stream on
            # device; composing them (draft windows inside a fused
            # window) is ROADMAP work, not a silent interaction
            raise ValueError(
                "multi_step > 1 and spec_decode are mutually "
                "exclusive: speculative verify windows re-plan every "
                "step from collected acceptance truth, which a fused "
                "k-step program cannot observe mid-window")
        self.multi_step = multi_step
        # -- quantized KV cache (ISSUE 13) ----------------------------------
        # kv_quant="int8" stores the paged pool's k/v planes as int8
        # with per-slot-per-kv-head absmax scales in a sidecar plane:
        # quantize is fused into every append (reshape_and_cache),
        # dequant into every pool read (the ragged Pallas kernel's
        # per-page DMA and the jnp oracle's page walk alike). Roughly
        # halves KV bytes per token (bf16 pools; ~3.6x on f32), so the
        # same HBM holds ~2x the concurrent sequences / resident
        # adapters. None (the default) is the dense pool, bitwise
        # unchanged. ACCURACY CONTRACT: greedy outputs match the fp32
        # pool on the pinned workloads (quantization noise is well
        # below typical logit gaps; a sub-quantization-step near-tie
        # may legitimately flip — that is the flag's contract, same as
        # tp_comm="int8"); note the dense and ragged SCHEDULERS are
        # each deterministic under kv_quant but not bit-identical to
        # each other (dense prefill attends the chunk's fresh
        # full-precision K/V, the ragged path reads its own rows back
        # quantized).
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be None or 'int8', got "
                             f"{kv_quant!r}")
        if tp > 1 and mesh is not None:
            raise ValueError("pass either tp=N (manual shard_map "
                             "serving) or mesh= (GSPMD decoder "
                             "placement), not both")
        if isinstance(model, (PagedLlamaDecoder, PagedGPTDecoder)):
            # a prebuilt paged decoder (e.g. PagedLlamaDecoder
            # .from_config for 8B-class weights that must be quantized
            # at load); its pool/quantization/tp choices stand — the
            # num_blocks/block_size/weight_dtype args here are ignored
            if devices is not None:
                raise ValueError(
                    "devices= only applies when the engine builds the "
                    "decoder itself; a prebuilt decoder's mesh already "
                    "fixed its device placement")
            self.dec = model
            dec_tp = int(getattr(model, "_tp", 1))
            if tp > 1 and dec_tp != tp:
                raise ValueError(
                    f"ServingEngine(tp={tp}) got a prebuilt decoder "
                    f"with tp degree {dec_tp}; build the decoder with "
                    f"the matching mesh (tp_shard_map=True) or drop "
                    f"the engine tp argument")
            dec_comm = getattr(model, "tp_comm", "fp32")
            if tp_comm is not None and dec_comm != tp_comm:
                # the comm mode is baked into the decoder's programs:
                # silently substituting the decoder's would run the
                # wrong leg of the fp32-vs-int8 A/B in EITHER direction
                raise ValueError(
                    f"ServingEngine(tp_comm={tp_comm!r}) got a "
                    f"prebuilt decoder built with tp_comm="
                    f"{dec_comm!r}; pass the desired tp_comm to the "
                    f"decoder constructor instead")
            dec_kvq = getattr(model.cache, "kv_quant", None)
            if kv_quant is not None and dec_kvq != kv_quant:
                # same contract as tp_comm: the pool layout is baked
                # into the decoder's cache and compiled programs — a
                # silently-substituted mode would run the wrong leg of
                # the fp32-vs-int8 capacity/accuracy A/B
                raise ValueError(
                    f"ServingEngine(kv_quant={kv_quant!r}) got a "
                    f"prebuilt decoder whose pool was built with "
                    f"kv_quant={dec_kvq!r}; pass the desired kv_quant "
                    f"to the decoder constructor instead")
            self.tp = dec_tp
        else:
            if devices is not None and tp == 1:
                # fail loudly, like the PR-8 tp-flag checks: a tp=1
                # engine always builds on the default device, and a
                # silently-dropped placement request would put every
                # "placed" fleet replica on one chip with no hint why
                raise ValueError(
                    "devices= requires tp > 1: a single-chip engine "
                    "builds on the default device (the fleet Router "
                    "passes devices only for tp-sharded replicas)")
            if tp > 1:
                # devices=: an explicit device slice for the tp mesh —
                # the fleet Router (inference/fleet.py) places each
                # dp replica's tp mesh on a DISJOINT row of the
                # SpecLayout dp x tp device grid; the default remains
                # the first tp devices of the process
                devs = (list(devices) if devices is not None
                        else jax.devices())
                if len(devs) < tp:
                    raise ValueError(
                        f"tp={tp} needs {tp} devices, found "
                        f"{len(devs)}")
                from jax.sharding import Mesh
                mesh = Mesh(np.asarray(devs[:tp]), ("tp",))
            self.dec = PagedLlamaDecoder(model, num_blocks=num_blocks,
                                         block_size=block_size,
                                         weight_dtype=weight_dtype,
                                         mesh=mesh, mp_axis="tp"
                                         if tp > 1 else "mp",
                                         tp_shard_map=tp > 1,
                                         tp_comm=tp_comm or "fp32",
                                         kv_quant=kv_quant)
            self.tp = tp
        self.tp_comm = getattr(self.dec, "tp_comm", tp_comm or "fp32")
        # the pool's actual quantization mode (prebuilt decoders carry
        # their own; None = dense fp planes) — surfaced by stats()
        self.kv_quant = getattr(self.dec.cache, "kv_quant", None)
        self.max_b = int(max_batch_size)
        self.buckets = tuple(sorted(prompt_buckets))
        self.top_k = int(top_k)
        # chunk ladder (adaptive decode granularity): each dispatch
        # picks a rung via _pick_chunk — after warmup, the rung
        # maximizing measured tokens/sec for the current slot budgets
        # (big chunks amortize host round trips; small chunks keep slot
        # turnover and admission prompt). Single-entry schedule (the
        # default) = fixed chunk.
        if chunk_schedule:
            self.chunks = tuple(sorted({max(1, int(c))
                                        for c in chunk_schedule}))
        else:
            self.chunks = (max(1, int(chunk_size)),)
        self.chunk = self.chunks[0]
        # overlap: dispatch decode chunk t+1 (first tokens taken from
        # chunk t's DEVICE output) before fetching chunk t's tokens, so
        # host admission/bookkeeping runs while the device decodes.
        # Falls back to synchronous collection while any active request
        # uses repetition_penalty (its seen-mask needs fetched history).
        self.overlap = bool(overlap)
        self._key = jax.random.PRNGKey(seed)
        cache = self.dec.cache
        # reserve one scratch page: pad-token prefill writes and inactive
        # decode slots land here, never in a live page (a prebuilt
        # decoder reused across engines keeps its existing scratch page)
        if -1 not in cache._tables:
            cache.allocate(-1, 1)
        self._scratch_block = cache._tables[-1][0]
        self._scratch_slot = self._scratch_block * cache.block_size
        # automatic prefix caching: block-granular KV reuse on admission
        # (needs the decoder's suffix-prefill program — prebuilt
        # decoders without one fall back to full prefills)
        self.prefix_caching = bool(prefix_caching) and \
            hasattr(self.dec, "_prefill_prefix_impl")
        # chunked prefill (the stall-free interleaving path): suffixes
        # longer than prefill_chunk split into fixed-size chunks that
        # interleave with decode chunks. Needs the decoder's chunk
        # program; prefill_chunk=None restores monolithic prefill
        # (whole suffix in one dispatch — still queued/async, so the
        # ONLY behavioral difference is the device-side interleaving).
        self.prefill_chunk = (int(prefill_chunk)
                              if prefill_chunk and
                              hasattr(self.dec, "_prefill_chunk_impl")
                              else None)
        # per-step prefill token budget while decodes are running
        # (idle engines dispatch every ready chunk): at most ~budget
        # prefill tokens slot between consecutive decode chunks, which
        # is the running streams' worst-case added inter-token latency
        self.prefill_budget = max(1, int(prefill_budget)) \
            if prefill_budget else (self.prefill_chunk or 0)
        # -- fault tolerance ------------------------------------------------
        # bounded retry with exponential backoff around every device
        # dispatch/fetch: a transient error re-tries the SAME call
        # (same args, same PRNG key — token-identical on success);
        # exhaustion fails the involved requests, never the engine.
        self.max_dispatch_retries = max(0, int(max_dispatch_retries))
        self.retry_backoff_s = float(retry_backoff_s)
        # admission policy: "worst_case" reserves prompt+max_new pages
        # up front (a running request can never hit pool exhaustion —
        # the PR-1 invariant); "optimistic" reserves only the prefill's
        # pages and grows on demand, oversubscribing the pool — under
        # pressure the engine preempts the newest/lowest-priority
        # running request (frees its blocks, re-enqueues it as a
        # no-sample chunked re-prefill that rides the prefix cache).
        if admission not in ("worst_case", "optimistic"):
            raise ValueError(
                f"admission must be 'worst_case' or 'optimistic', "
                f"got {admission!r}")
        self.admission = admission
        self.max_queue_depth = (int(max_queue_depth)
                                if max_queue_depth is not None else None)
        # robustness counters (stats(); reset by clear_finished)
        self.preemptions = 0
        self.recompute_tokens = 0
        self.aborted = 0
        self.failed = 0
        self.deadline_misses = 0
        self.shed_requests = 0
        self.retries = 0
        # dispatch/fetch calls that exhausted their whole retry budget
        # (each one failed the involved requests). This is the fleet
        # Router's primary per-replica health signal: a replica whose
        # engine keeps exhausting _device_call retries is wedged, not
        # merely flaky (reset by clear_finished)
        self.dispatch_exhaustions = 0
        # device-program launch count (every successful "dispatch:*"
        # _device_call — prefill, decode, merge, ragged, spec); with
        # generated_tokens it yields tokens_per_dispatch, the headline
        # the ragged path optimizes and speculative decoding multiplies
        # (accepted draft tokens are generated_tokens too, so the
        # metric reflects the win; reset by clear_finished)
        self.device_dispatches = 0
        # speculative-decoding counters (ISSUE 9; reset by
        # clear_finished): drafted = draft rows dispatched for
        # verification, accepted = drafts confirmed by the teacher,
        # spec_rollbacks = verify steps that rejected >= 1 draft (each
        # costs one PagedKVCache.rollback of the rejected tail)
        self.drafted_tokens = 0
        self.accepted_draft_tokens = 0
        self.spec_rollbacks = 0
        # optional chaos monkey (utils/chaos.py ChaosMonkey.attach):
        # consulted by _device_call before every dispatch/fetch
        self.chaos = None
        # static prefix-gather width: a hit prefix is < the prompt, and
        # prompts are bounded by the largest bucket
        self._prefix_pages = -(-self.buckets[-1] // cache.block_size)
        # mid-chunk prefix widths are power-of-two BUCKETED: chunk i's
        # prefix is only i*C tokens, and paying the max-bucket gather +
        # masked attention on every chunk made early chunks cost as
        # much as late ones (the chunk program is width-1 and runs
        # O(prompt/C) times per long prompt, so ~log2 variants are
        # cheap; the one-shot final keeps the single max-width program
        # shared with the prefix-cache-hit path)
        self._prefix_page_buckets = []
        p = 1
        while p < self._prefix_pages:
            self._prefix_page_buckets.append(p)
            p *= 2
        self._prefix_page_buckets.append(self._prefix_pages)
        # recompute prefills (preemption resume) run at offsets up to
        # prompt + generated history — past the largest prompt bucket —
        # so the mid-chunk prefix ladder continues doubling up to the
        # longest table a single sequence can hold. Entries after
        # _prefix_pages are only ever reached by resumes, so the
        # pre-existing bucket choices (and compiled variants) of the
        # normal chunked-prefill path are unchanged.
        cap_pages = min(self.dec.max_pages,
                        max(1, cache.num_blocks - 1))
        while p < cap_pages and self._prefix_page_buckets[-1] < cap_pages:
            if p > self._prefix_page_buckets[-1]:
                self._prefix_page_buckets.append(min(p, cap_pages))
            p *= 2
        if self._prefix_page_buckets[-1] < cap_pages:
            self._prefix_page_buckets.append(cap_pages)
        # chunk width for preemption-resume prefills: ride the chunked-
        # prefill programs when enabled, else a dedicated 64-wide rung
        self._recompute_chunk = self.prefill_chunk or 64
        self._debug_pool = os.environ.get(
            "PADDLE_TPU_POOL_DEBUG", "") not in ("", "0")
        # schedule-array staging: under manual tp the per-chunk arrays
        # must reach the program UNCOMMITTED (np) — jnp.asarray would
        # commit them to the default device, which conflicts with the
        # tp mesh; jit places uncommitted arrays per the shard_map
        # in_specs (replicated) itself
        self._aj = jnp.asarray if self.tp == 1 else np.asarray

        self._slots: List[Optional[Request]] = [None] * self.max_b
        self._last_tok = np.zeros(self.max_b, np.int32)
        self._queue: deque = deque()
        self._done: Dict[int, Request] = {}
        self._ids = itertools.count()
        self.decode_steps = 0
        self.generated_tokens = 0
        # decode-utilization accounting (chunk-ladder tuning): a decode
        # dispatch runs T steps x max_b slots regardless of how many
        # slots had real work — slot_steps counts everything the
        # program ran, useful_tokens what reached a request
        self.decode_slot_steps = 0
        self.decode_useful_tokens = 0
        # splice-pending writer index: block -> (writer request, suffix
        # tokens the writer must dispatch for the block to be written);
        # entries live only while the writer is mid-prefill
        self._pending_writes: Dict[int, Tuple[Request, int]] = {}
        # async pipeline state (overlap mode): dispatched, unfetched
        # prefill AND decode chunks, in device program order
        self._inflight: deque = deque()
        self._fresh_slots: set = set()    # slots (re)filled since the
        #                                   last dispatch: their first
        #                                   token comes from the host
        # phase-time breakdown (bench: prefill / decode-stall / host)
        self.time_prefill_s = 0.0
        self.time_stall_s = 0.0
        self.time_host_s = 0.0
        self.time_by_phase_s = {}
        self._ph = None             # the phase a @_phased method runs as
        self._step_seq = 0          # engine.step spans' step=
        self._dispatch_seq = 0      # device programs launched, ever
        self._seq_cur = 0           # the one last launched or fetched:
        #                             the dispatch= of request spans
        self._zeros_seen_cache: Dict[int, jax.Array] = {}
        # per-rung measured chunk cost (seconds/chunk), built by warmup;
        # empty → _pick_chunk uses the zero-waste heuristic
        self._chunk_cost: Dict[int, float] = {}
        self._force_chunk: Optional[int] = None

        dec = self.dec

        def prefill(weights, k, v, ids, slots, last_idx, temp, key,
                    top_ks, top_ps, rep, seen, allowed):
            logits, k, v = dec._prefill_impl(weights, k, v, ids, slots,
                                             last_idx)
            tok = self._sample_rich(logits, temp, key, top_ks, top_ps,
                                    rep, seen, allowed)
            return tok, k, v

        def prefill_prefix(weights, k, v, ids, slots, last_idx,
                           n_cached, prefix_tables, temp, key, top_ks,
                           top_ps, rep, seen, allowed):
            logits, k, v = dec._prefill_prefix_impl(
                weights, k, v, ids, slots, last_idx, n_cached,
                prefix_tables)
            tok = self._sample_rich(logits, temp, key, top_ks, top_ps,
                                    rep, seen, allowed)
            return tok, k, v

        def decode_chunk(weights, k, v, first_ids, tables_all, ctx_all,
                         slots_all, temp, keys_all):
            """T decode steps as one lax.scan (one dispatch per chunk)."""
            def step(carry, xs):
                last_ids, kp, vp = carry
                tables, ctx, slots, key = xs
                logits, kp, vp = dec._decode_logits(
                    weights, kp, vp, last_ids, tables, ctx, slots)
                nxt = self._sample(logits, temp, key)
                return (nxt, kp, vp), nxt
            (_, k, v), toks = jax.lax.scan(
                step, (first_ids, k, v),
                (tables_all, ctx_all, slots_all, keys_all))
            return toks.swapaxes(0, 1), k, v   # [b, T]

        def decode_chunk_rich(weights, k, v, first_ids, tables_all,
                              ctx_all, slots_all, temp, keys_all,
                              top_ks, top_ps, rep, seen, allowed):
            """Per-request-sampling variant: the scan additionally
            carries the token-presence mask (repetition penalty) and
            applies per-slot top_k/top_p masks plus the per-slot
            allowed-vocab mask (structured decoding). Compiled only
            when a request actually asks for them."""
            def step(carry, xs):
                last_ids, kp, vp, seen_c = carry
                tables, ctx, slots, key = xs
                logits, kp, vp = dec._decode_logits(
                    weights, kp, vp, last_ids, tables, ctx, slots)
                nxt = self._sample_rich(logits, temp, key, top_ks,
                                        top_ps, rep, seen_c, allowed)
                seen_c = seen_c.at[
                    jnp.arange(seen_c.shape[0]), nxt].set(True)
                return (nxt, kp, vp, seen_c), nxt
            (_, k, v, _), toks = jax.lax.scan(
                step, (first_ids, k, v, seen),
                (tables_all, ctx_all, slots_all, keys_all))
            return toks.swapaxes(0, 1), k, v   # [b, T]

        def merge_first(toks_dev, last_idx, overrides, use_host):
            """First tokens of the next chunk from the previous chunk's
            device output (continuing slots) or host values (fresh
            slots) — keeps the chunk-to-chunk dependency on-device."""
            gathered = toks_dev[jnp.arange(toks_dev.shape[0]), last_idx]
            return jnp.where(use_host, overrides, gathered)

        self._prefill_j = jax.jit(prefill, donate_argnums=(1, 2))
        self._prefill_prefix_j = jax.jit(prefill_prefix,
                                         donate_argnums=(1, 2))
        self._decode_j = jax.jit(decode_chunk, donate_argnums=(1, 2))
        self._decode_rich_j = jax.jit(decode_chunk_rich,
                                      donate_argnums=(1, 2))
        self._merge_first_j = jax.jit(merge_first)
        if hasattr(dec, "_prefill_chunk_impl"):
            # no-sample chunk programs (width 1, exactly prefill_chunk
            # tokens; prefill_mid retraces per power-of-two prefix-
            # width bucket — ~log2(prefix_pages) variants — plus one
            # cold-start prefill_mid0): mid chunks only write K/V, so
            # the wrappers drop the logits and XLA DCEs the head
            # matmul; no PRNG key is consumed. Built even with chunked
            # prefill OFF: preemption-with-recompute re-prefills a
            # preempted request's history through these (the resume
            # must not draw PRNG keys, or every other request's
            # sampled stream would shift vs a fault-free run).
            def prefill_mid(weights, k, v, ids, slots, n_cached, ptab):
                return dec._prefill_chunk_impl(weights, k, v, ids,
                                               slots, n_cached, ptab)

            def prefill_mid0(weights, k, v, ids, slots):
                _, k, v = dec._prefill_impl(weights, k, v, ids, slots)
                return k, v

            self._prefill_mid_j = jax.jit(prefill_mid,
                                          donate_argnums=(1, 2))
            self._prefill_mid0_j = jax.jit(prefill_mid0,
                                           donate_argnums=(1, 2))
        self._can_recompute = hasattr(dec, "_prefill_chunk_impl")

        # -- ragged unified prefill+decode batching (ISSUE 5) ---------------
        # ragged=True collapses every per-step dispatch into ONE device
        # program: a [T, W] schedule of flattened ragged rows — decode
        # rows (one column per running slot, T sequential ministeps,
        # sampled in-program with the previous chunk's device output
        # merged IN-program, so there is no separate merge dispatch) and
        # prefill rows (no-sample mid-chunk rows at their global offsets;
        # a prompt's final token row samples the request's first token).
        # W is sized by the ACTUAL rows (bucketed), not max_batch — the
        # dense path's scratch-slot padding disappears at the source.
        # Needs the decoder's _ragged_logits; the attention op falls
        # back to the masked jnp oracle off-TPU.
        self.ragged = bool(ragged) and hasattr(dec, "_ragged_logits")
        if self.tp > 1:
            if not hasattr(dec, "_ragged_logits"):
                raise ValueError(
                    "tensor-parallel serving needs a decoder with the "
                    "ragged step program (_ragged_logits)")
            # the tp serving step IS the sharded ragged program; the
            # dense per-phase dispatch path is not built for shard_map
            self.ragged = True
        if self.multi_step > 1:
            if not hasattr(dec, "_ragged_logits"):
                raise ValueError(
                    "multi-step fused decode needs a decoder with the "
                    "ragged step program (_ragged_logits)")
            # the fused window IS a ragged [k*T, W] program
            self.ragged = True
        # -- speculative decoding (ISSUE 9) ---------------------------------
        # spec_decode=SpecConfig(...): each greedy decode column's k
        # draft tokens ride as EXTRA ROWS of the ragged program (the
        # mechanism prefill-chunk rows already use) and are verified
        # in-program — teacher logits at every draft position in ONE
        # forward, longest-accepted-prefix acceptance, rejected tails'
        # pool writes neutralized via the scratch page and their slots
        # rescinded by PagedKVCache.rollback. Up to draft_len + 1
        # verified tokens per column per dispatch; greedy outputs are
        # BIT-IDENTICAL to the spec-off path (each emitted token is
        # the teacher's own argmax under a verified prefix). Forces
        # the ragged path: the verify window IS a ragged row pattern.
        # -- multi-tenant many-LoRA serving (ISSUE 10) ----------------------
        # lora=AdapterRegistry(...): per-request adapters ride the
        # ragged [T, W] program as per-row (A, B) deltas gathered from
        # adapter pages paged through the SAME block pool as the KV
        # cache (S-LoRA style — see inference/lora.py). Forces the
        # ragged path: the per-row adapter index IS a ragged-row
        # attribute. Dispatches whose scheduled requests are all
        # base-model use the UNCHANGED base programs, so adapter_id=
        # None traffic is bit-identical to a lora-less engine.
        self.lora = lora
        if lora is not None:
            from .lora import AdapterRegistry
            if not isinstance(lora, AdapterRegistry):
                raise TypeError(
                    f"lora must be an AdapterRegistry, got "
                    f"{type(lora).__name__}")
            if not hasattr(dec, "_ragged_logits") \
                    or not hasattr(dec, "lora_target_modules"):
                raise ValueError(
                    "many-LoRA serving needs a decoder with the ragged "
                    "step program and LoRA targets (_ragged_logits + "
                    "lora_target_modules)")
            self.ragged = True
            if self.tp > 1:
                # the plane's placement comes from the canonical
                # SpecLayout table (replicated), like every other
                # sharded serving array
                lora.bind(dec, sharding=dec._layout().sharding(
                    dec.mesh, "lora_pool"))
            else:
                lora.bind(dec)
        # per-shard index operand for the lora programs: a tp-sharded
        # arange whose in-program element is the shard id (the repo's
        # axis_index idiom — jax 0.4.x-safe); a plain [0] off tp
        if self.tp > 1:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P
            self._shard_ids = jax.device_put(
                np.arange(self.tp, dtype=np.int32),
                NamedSharding(self.dec.mesh, P("tp")))
        else:
            self._shard_ids = np.zeros(1, np.int32)
        # multi-tenant / structured-decoding counters (stats(); reset
        # by clear_finished): lora_dispatches / lora_rows feed
        # lora_rows_per_dispatch; masked_decode_columns counts
        # scheduled decode columns carrying an allowed_tokens mask
        self.lora_dispatches = 0
        self.lora_rows = 0
        self.masked_decode_columns = 0
        # multi-step fused decode counters (stats(); reset by
        # clear_finished): windows dispatched, and slot-steps a fused
        # window scheduled but froze after an in-window EOS (the
        # honest frozen-column share of padded_token_waste)
        self.ms_windows = 0
        self.ms_frozen_token_waste = 0
        self._ones_allowed_cache: Dict[int, jax.Array] = {}
        # composed allowed-mask operands, memoized per (rows, row ->
        # mask-identity) layout: a request's mask is immutable, so a
        # steady-state masked stream re-ships nothing (cleared by
        # clear_finished — mask ids are only stable while their
        # requests are retained)
        self._allowed_memo: Dict[tuple, jax.Array] = {}
        # -- telemetry (ISSUE 12) -------------------------------------------
        # tracer=None (the default) is a bitwise no-op: every hook is
        # behind an `if self.tracer is not None` guard, no PRNG key is
        # drawn and no schedule array changes. set_telemetry also
        # threads the tracer into the KV pool and the adapter registry
        # so kv alloc/evict/splice/rollback and adapter refaults land
        # in the same flight recorder; the fleet Router re-calls it
        # with the replica index so every record carries its replica.
        # -- program observatory (ISSUE 14) ---------------------------------
        # CompileWatch: every serving program family registers its
        # jitted callable (end of __init__, once the programs exist);
        # _device_call asks the watch after each dispatch whether the
        # jit cache grew — a grown cache IS a trace+lower+compile,
        # recorded as a compile span. seal_programs() (after
        # warmup_programs has compiled the reachable grid) turns any
        # later compile into engine.unexpected_recompiles — the
        # runtime analogue of flightcheck's FC2xx rules. The watch is
        # always on: detection is two host attribute reads per
        # dispatch, and chaos legs must be able to assert the sealed
        # contract even when no tracer is attached.
        self.compile_watch = CompileWatch()
        self.unexpected_recompiles = 0
        self.program_compiles = 0
        # sampled dispatch-time attribution: every profile_every-th
        # dispatch pays a block_until_ready fence (seeded start phase)
        # and splits the step wall into host-schedule / dispatch-queue
        # / device-execute histograms, per program family. Default OFF
        # — the unsampled steady state keeps the async pipeline and
        # the bitwise no-op contract (a fence never changes tokens,
        # but it does cost a sync, so sampling is opt-in).
        if profile_every is not None and int(profile_every) < 1:
            raise ValueError(f"profile_every must be >= 1, got "
                             f"{profile_every}")
        self._prof_n = int(profile_every) if profile_every else 0
        self._prof_metrics = None    # lazy registry when tracer is off
        self._prof_countdown = 0
        if self._prof_n:
            rng = np.random.RandomState(int(profile_seed))
            self._prof_countdown = 1 + int(rng.randint(self._prof_n))
        self._prof_mark = time.perf_counter()
        self.profiled_dispatches = 0
        # SLO monitoring (declared per-class latency targets; see
        # telemetry.SLOPolicy/SLOMonitor): fed at the same collection
        # points as the PR-12 histograms, evaluated by stats()["slo"].
        # Pure host-side and passive — attaching a monitor changes no
        # schedule, draws no key.
        if isinstance(slo, SLOMonitor):
            self._slo = slo
        else:
            pols = SLOMonitor.coerce_policies(slo)
            self._slo = SLOMonitor(pols) if pols else None
        self._slo_violating: set = set()
        # per-window draft-acceptance EMA (alpha 0.1): the adaptive-
        # window signal ROADMAP item 2 needs, sampled into the
        # acceptance_ema counter track
        self.draft_acceptance_ema = 0.0
        self.set_telemetry(tracer)
        # bounded ITL aggregation (ISSUE 12 satellite): finished
        # requests' per-token samples fold into a seeded reservoir at
        # retire time, so stats() percentiles stay O(k) on unbounded
        # runs (exact below capacity; sampling-tolerance above it).
        # Live requests' samples are still read exactly from the slot.
        self._itl_res = Reservoir(self.ITL_RESERVOIR_K)
        self.spec = spec_decode
        self._drafter = None
        if self.spec is not None:
            if not isinstance(self.spec, SpecConfig):
                raise TypeError(
                    f"spec_decode must be a SpecConfig, got "
                    f"{type(self.spec).__name__}")
            if not (hasattr(dec, "_ragged_logits")
                    and hasattr(dec, "_spec_accept")):
                raise ValueError(
                    "speculative decoding needs a decoder with the "
                    "ragged step program and the verification tail "
                    "(_ragged_logits + _spec_accept)")
            self.ragged = True
            self._drafter = self.spec.make_drafter()
        # prefill tokens folded into one ragged dispatch (the ragged
        # path is always chunked-style — a long prompt spreads over
        # successive steps' programs under this per-step cap)
        self._ragged_cap = (self.prefill_budget or self.prefill_chunk
                            or self._recompute_chunk)
        # idle-drain width bound (ISSUE 14): pure-prefill programs on
        # an idle engine widen up to this many rows per dispatch. The
        # class default keeps the PR-5 wide-drain behavior; a bounded
        # value CLOSES the reachable (T, W) program grid so
        # warmup_programs can compile it whole and seal_programs can
        # assert no mid-run retrace (the chaos legs run bounded)
        if ragged_idle_cap is not None and int(ragged_idle_cap) < 1:
            raise ValueError(f"ragged_idle_cap must be >= 1, got "
                             f"{ragged_idle_cap}")
        self._ragged_idle_cap = (int(ragged_idle_cap)
                                 if ragged_idle_cap is not None
                                 else self._RAGGED_IDLE_CAP)
        self._zeros_toks_cache: Dict[Tuple[int, int], jax.Array] = {}
        if self.ragged:
            def ragged_chunk(weights, k, v, prev_toks, last_t, prev_col,
                             use_host, override, ids_all, pos_all,
                             slots_all, rseq_all, rctx_all, use_carry,
                             tables, temps_all, keys):
                """T ragged ministeps as one lax.scan. Decode columns
                carry their sampled token ministep-to-ministep on
                device; their FIRST token is gathered from the previous
                ragged chunk's [T, W] output (continuing columns) or a
                host override (fresh slots) — the dense path's
                merge_first folded into the program."""
                first = jnp.where(use_host, override,
                                  prev_toks[last_t, prev_col])

                def step(carry, xs):
                    cur, kp, vp = carry
                    ids_d, pos, slots, rseq, rctx, uc, temp, key = xs
                    ids = jnp.where(uc, cur, ids_d)
                    logits, kp, vp = dec._ragged_logits(
                        weights, kp, vp, ids, pos, slots, rseq, rctx,
                        tables)
                    nxt = self._sample(logits, temp, key)
                    return (nxt, kp, vp), nxt

                (_, k, v), toks = jax.lax.scan(
                    step, (first, k, v),
                    (ids_all, pos_all, slots_all, rseq_all, rctx_all,
                     use_carry, temps_all, keys))
                return toks, k, v          # [T, W]

            def ragged_chunk_rich(weights, k, v, prev_toks, last_t,
                                  prev_col, use_host, override, ids_all,
                                  pos_all, slots_all, rseq_all,
                                  rctx_all, use_carry, tables,
                                  temps_all, keys, top_ks_all,
                                  top_ps_all, reps_all, seen, upd,
                                  allowed):
                """Per-request-sampling twin: carries the seen mask.
                Only columns flagged in `upd` (decode columns)
                accumulate their own samples — a final-prefill row's
                seen mask is its prompt, seeded host-side, and other
                ministeps sharing its column must not pollute it.
                ``allowed`` [W, vocab] is per COLUMN (ministep-
                invariant): only the column's consumed cells — its
                decode samples or its one sampling final — ever reach
                a request, so masking the discarded cells too is
                harmless."""
                first = jnp.where(use_host, override,
                                  prev_toks[last_t, prev_col])
                w = use_host.shape[0]

                def step(carry, xs):
                    cur, kp, vp, seen_c = carry
                    (ids_d, pos, slots, rseq, rctx, uc, temp, key,
                     tks, tps, rp) = xs
                    ids = jnp.where(uc, cur, ids_d)
                    logits, kp, vp = dec._ragged_logits(
                        weights, kp, vp, ids, pos, slots, rseq, rctx,
                        tables)
                    nxt = self._sample_rich(logits, temp, key, tks,
                                            tps, rp, seen_c, allowed)
                    rows = jnp.arange(w)
                    seen_c = seen_c.at[rows, nxt].set(
                        seen_c[rows, nxt] | upd)
                    return (nxt, kp, vp, seen_c), nxt

                (_, k, v, _), toks = jax.lax.scan(
                    step, (first, k, v, seen),
                    (ids_all, pos_all, slots_all, rseq_all, rctx_all,
                     use_carry, temps_all, keys, top_ks_all,
                     top_ps_all, reps_all))
                return toks, k, v          # [T, W]

            if self.tp > 1:
                # the WHOLE step program — decode scan, in-program
                # sampling, KV append, prefill rows — runs fully-manual
                # under shard_map on the tp mesh (jax 0.4.x cannot
                # lower collectives in a partially-manual region; the
                # one-axis serving mesh makes full specs natural)
                self._ragged_j = jax.jit(
                    dec.tp_wrap(ragged_chunk, n_extra=14),
                    donate_argnums=(1, 2))
                self._ragged_rich_j = jax.jit(
                    dec.tp_wrap(ragged_chunk_rich, n_extra=20),
                    donate_argnums=(1, 2))
            else:
                self._ragged_j = jax.jit(ragged_chunk,
                                         donate_argnums=(1, 2))
                self._ragged_rich_j = jax.jit(ragged_chunk_rich,
                                              donate_argnums=(1, 2))

            if self.lora is not None:
                layout = self.lora.layout

                def _lora_ctx(lora_pool, shard_ids, lora_tables):
                    """Gather each engine slot's adapter pages out of
                    the shared pool plane ONCE per dispatch (scan-
                    invariant): [S, n_pages * page_elems] flat factors
                    the decoder's static layout slices — S = max_b + 1
                    rows addressed by row_seq, the scratch row reading
                    the scratch block's all-zero page (the null
                    adapter every base-only row costs)."""
                    # bounded, deliberate: S * n_pages adapter pages
                    # (the slots' own tables, not the pool), gathered
                    # once per dispatch outside the decode scan
                    flat = jnp.take(  # flightcheck: disable=FC701
                        lora_pool, lora_tables.reshape(-1),
                        axis=0, mode="clip")
                    flat = flat.reshape(lora_tables.shape[0], -1)
                    return (layout, flat, shard_ids[0])

                def ragged_lora_chunk(weights, k, v, lora_pool,
                                      shard_ids, lora_tables,
                                      prev_toks, last_t, prev_col,
                                      use_host, override, ids_all,
                                      pos_all, slots_all, rseq_all,
                                      rctx_all, use_carry, tables,
                                      temps_all, keys):
                    """ragged_chunk with per-row LoRA deltas: the
                    multi-tenant twin — same schedule contract, one
                    program per step, adapters applied inside
                    _ragged_logits via the gathered page factors."""
                    lctx = _lora_ctx(lora_pool, shard_ids, lora_tables)
                    first = jnp.where(use_host, override,
                                      prev_toks[last_t, prev_col])

                    def step(carry, xs):
                        cur, kp, vp = carry
                        ids_d, pos, slots, rseq, rctx, uc, temp, key \
                            = xs
                        ids = jnp.where(uc, cur, ids_d)
                        logits, kp, vp = dec._ragged_logits(
                            weights, kp, vp, ids, pos, slots, rseq,
                            rctx, tables, lora=lctx)
                        nxt = self._sample(logits, temp, key)
                        return (nxt, kp, vp), nxt

                    (_, k, v), toks = jax.lax.scan(
                        step, (first, k, v),
                        (ids_all, pos_all, slots_all, rseq_all,
                         rctx_all, use_carry, temps_all, keys))
                    return toks, k, v          # [T, W]

                def ragged_lora_chunk_rich(weights, k, v, lora_pool,
                                           shard_ids, lora_tables,
                                           prev_toks, last_t, prev_col,
                                           use_host, override, ids_all,
                                           pos_all, slots_all,
                                           rseq_all, rctx_all,
                                           use_carry, tables,
                                           temps_all, keys, top_ks_all,
                                           top_ps_all, reps_all, seen,
                                           upd, allowed):
                    """ragged_chunk_rich with per-row LoRA deltas."""
                    lctx = _lora_ctx(lora_pool, shard_ids, lora_tables)
                    first = jnp.where(use_host, override,
                                      prev_toks[last_t, prev_col])
                    w = use_host.shape[0]

                    def step(carry, xs):
                        cur, kp, vp, seen_c = carry
                        (ids_d, pos, slots, rseq, rctx, uc, temp, key,
                         tks, tps, rp) = xs
                        ids = jnp.where(uc, cur, ids_d)
                        logits, kp, vp = dec._ragged_logits(
                            weights, kp, vp, ids, pos, slots, rseq,
                            rctx, tables, lora=lctx)
                        nxt = self._sample_rich(logits, temp, key, tks,
                                                tps, rp, seen_c,
                                                allowed)
                        rows = jnp.arange(w)
                        seen_c = seen_c.at[rows, nxt].set(
                            seen_c[rows, nxt] | upd)
                        return (nxt, kp, vp, seen_c), nxt

                    (_, k, v, _), toks = jax.lax.scan(
                        step, (first, k, v, seen),
                        (ids_all, pos_all, slots_all, rseq_all,
                         rctx_all, use_carry, temps_all, keys,
                         top_ks_all, top_ps_all, reps_all))
                    return toks, k, v          # [T, W]

                if self.tp > 1:
                    self._ragged_lora_j = jax.jit(
                        dec.tp_wrap(ragged_lora_chunk, n_extra=15,
                                    lora_pool=True),
                        donate_argnums=(1, 2))
                    self._ragged_lora_rich_j = jax.jit(
                        dec.tp_wrap(ragged_lora_chunk_rich, n_extra=21,
                                    lora_pool=True),
                        donate_argnums=(1, 2))
                else:
                    self._ragged_lora_j = jax.jit(
                        ragged_lora_chunk, donate_argnums=(1, 2))
                    self._ragged_lora_rich_j = jax.jit(
                        ragged_lora_chunk_rich, donate_argnums=(1, 2))

            if self.spec is not None:
                scratch = self._scratch_slot

                def spec_chunk(weights, k, v, override, use_ov, ids,
                               pos, slots, rseq, rctx, tables, temps,
                               key, seg_start, is_draft):
                    """ONE speculative verify+decode ministep over a
                    ragged [W] row batch: each decode column's carried
                    token plus its k draft rows at consecutive
                    positions (drafts condition on each other through
                    the pool — write-before-attend + row_ctx, the
                    prefill-chunk mechanism), prefill rows riding
                    along as usual. Per-row sampling gives the
                    teacher's token at every position in one forward;
                    the decoder's _spec_accept computes the
                    longest-accepted-prefix mask in-program and
                    neutralizes rejected rows' pool writes via the
                    scratch slot. No scan: acceptance decides the next
                    input token, so a verify chunk is one ministep and
                    the host schedules the next from collected truth.
                    """
                    ids_in = jnp.where(use_ov, override, ids)
                    logits, k, v = dec._ragged_logits(
                        weights, k, v, ids_in, pos, slots, rseq, rctx,
                        tables)
                    toks = self._sample(logits, temps, key)
                    acc, k, v = dec._spec_accept(
                        k, v, toks, ids, slots, seg_start, is_draft,
                        scratch)
                    return toks, acc, k, v

                if self.tp > 1:
                    # verification must stay one-allreduce-per-block:
                    # _spec_accept compares post-gather (replicated)
                    # tokens and zero-scatters per-shard kv-head
                    # slices, so the sharded verify program has
                    # EXACTLY the T=1 ragged program's collectives
                    # (pinned by comm_audit serving.ragged_spec_tp2)
                    self._spec_j = jax.jit(
                        dec.tp_wrap(spec_chunk, n_extra=12,
                                    outs="takv"),
                        donate_argnums=(1, 2))
                else:
                    self._spec_j = jax.jit(spec_chunk,
                                           donate_argnums=(1, 2))

                if self.lora is not None:
                    def spec_lora_chunk(weights, k, v, lora_pool,
                                        shard_ids, lora_tables,
                                        override, use_ov, ids, pos,
                                        slots, rseq, rctx, tables,
                                        temps, key, seg_start,
                                        is_draft):
                        """spec_chunk with per-row LoRA deltas: draft
                        rows verify against the ROW's adapter model
                        (base + its tenant's delta), so acceptance is
                        exact per tenant; the acceptance tail is
                        adapter-agnostic."""
                        lctx = _lora_ctx(lora_pool, shard_ids,
                                         lora_tables)
                        ids_in = jnp.where(use_ov, override, ids)
                        logits, k, v = dec._ragged_logits(
                            weights, k, v, ids_in, pos, slots, rseq,
                            rctx, tables, lora=lctx)
                        toks = self._sample(logits, temps, key)
                        acc, k, v = dec._spec_accept(
                            k, v, toks, ids, slots, seg_start,
                            is_draft, scratch)
                        return toks, acc, k, v

                    if self.tp > 1:
                        self._spec_lora_j = jax.jit(
                            dec.tp_wrap(spec_lora_chunk, n_extra=13,
                                        outs="takv", lora_pool=True),
                            donate_argnums=(1, 2))
                    else:
                        self._spec_lora_j = jax.jit(
                            spec_lora_chunk, donate_argnums=(1, 2))

            if self.multi_step > 1:
                ms_scratch = self._scratch_slot

                def ragged_ms_chunk(weights, k, v, prev_toks, last_t,
                                    prev_col, use_host, override,
                                    ids_all, pos_all, slots_all,
                                    rseq_all, rctx_all, use_carry,
                                    tables, temps_all, keys, eos_ids):
                    """The fused k-step window (ISSUE 16): ragged_chunk
                    over k*T decode ministeps with ON-DEVICE EOS
                    bookkeeping. ``eos_ids`` [W] carries each column's
                    EOS token id (-1 = none); a per-column ``live``
                    mask rides the scan carry — once a column samples
                    its EOS, later iterations redirect its KV append
                    to the scratch slot (the write-neutralization
                    mechanism preemption already uses) and freeze its
                    carried token, so a finished column's remaining
                    ministeps are no-ops whose outputs the host
                    discards at the mid-chunk-EOS cut. The EOS token
                    itself IS delivered (the freeze applies from the
                    NEXT iteration), and its own KV never lands in
                    real pages — exactly the single-step schedule, so
                    greedy outputs are token-identical to
                    multi_step=1."""
                    first = jnp.where(use_host, override,
                                      prev_toks[last_t, prev_col])
                    live0 = jnp.ones(use_host.shape, bool)

                    def step(carry, xs):
                        cur, live, kp, vp = carry
                        ids_d, pos, slots, rseq, rctx, uc, temp, key \
                            = xs
                        ids = jnp.where(uc, cur, ids_d)
                        slots = jnp.where(live, slots, ms_scratch)
                        logits, kp, vp = dec._ragged_logits(
                            weights, kp, vp, ids, pos, slots, rseq,
                            rctx, tables)
                        nxt = self._sample(logits, temp, key)
                        nxt = jnp.where(live, nxt, cur)
                        live = live & (nxt != eos_ids)
                        return (nxt, live, kp, vp), nxt

                    (_, _, k, v), toks = jax.lax.scan(
                        step, (first, live0, k, v),
                        (ids_all, pos_all, slots_all, rseq_all,
                         rctx_all, use_carry, temps_all, keys))
                    return toks, k, v          # [k*T, W]

                def ragged_ms_chunk_rich(weights, k, v, prev_toks,
                                         last_t, prev_col, use_host,
                                         override, ids_all, pos_all,
                                         slots_all, rseq_all, rctx_all,
                                         use_carry, tables, temps_all,
                                         keys, eos_ids, top_ks_all,
                                         top_ps_all, reps_all, seen,
                                         upd, allowed):
                    """Per-request-sampling twin of the fused window:
                    the seen mask accumulates only while the column is
                    live (a frozen column's repeated carried token
                    must not re-mark itself — under multi_step=1 the
                    request retires before any such iteration runs)."""
                    first = jnp.where(use_host, override,
                                      prev_toks[last_t, prev_col])
                    live0 = jnp.ones(use_host.shape, bool)
                    w = use_host.shape[0]

                    def step(carry, xs):
                        cur, live, kp, vp, seen_c = carry
                        (ids_d, pos, slots, rseq, rctx, uc, temp, key,
                         tks, tps, rp) = xs
                        ids = jnp.where(uc, cur, ids_d)
                        slots = jnp.where(live, slots, ms_scratch)
                        logits, kp, vp = dec._ragged_logits(
                            weights, kp, vp, ids, pos, slots, rseq,
                            rctx, tables)
                        nxt = self._sample_rich(logits, temp, key, tks,
                                                tps, rp, seen_c,
                                                allowed)
                        nxt = jnp.where(live, nxt, cur)
                        rows = jnp.arange(w)
                        seen_c = seen_c.at[rows, nxt].set(
                            seen_c[rows, nxt] | (upd & live))
                        live = live & (nxt != eos_ids)
                        return (nxt, live, kp, vp, seen_c), nxt

                    (_, _, k, v, _), toks = jax.lax.scan(
                        step, (first, live0, k, v, seen),
                        (ids_all, pos_all, slots_all, rseq_all,
                         rctx_all, use_carry, temps_all, keys,
                         top_ks_all, top_ps_all, reps_all))
                    return toks, k, v          # [k*T, W]

                if self.tp > 1:
                    # tp_wrap'd like the base families: every operand
                    # past weights/k/v replicated, so tp=N multiplies
                    # the per-block collectives by EXACTLY k — pinned
                    # by comm_audit serving.ragged_k4_tp2
                    self._ragged_ms_j = jax.jit(
                        dec.tp_wrap(ragged_ms_chunk, n_extra=15),
                        donate_argnums=(1, 2))
                    self._ragged_ms_rich_j = jax.jit(
                        dec.tp_wrap(ragged_ms_chunk_rich, n_extra=21),
                        donate_argnums=(1, 2))
                else:
                    self._ragged_ms_j = jax.jit(
                        ragged_ms_chunk, donate_argnums=(1, 2))
                    self._ragged_ms_rich_j = jax.jit(
                        ragged_ms_chunk_rich, donate_argnums=(1, 2))

                if self.lora is not None:
                    def ragged_ms_lora_chunk(weights, k, v, lora_pool,
                                             shard_ids, lora_tables,
                                             prev_toks, last_t,
                                             prev_col, use_host,
                                             override, ids_all,
                                             pos_all, slots_all,
                                             rseq_all, rctx_all,
                                             use_carry, tables,
                                             temps_all, keys, eos_ids):
                        """ragged_ms_chunk with per-row LoRA deltas:
                        the adapter-page factors are gathered ONCE per
                        window (scan-invariant, PR 10's per-dispatch
                        state riding the fused scan)."""
                        lctx = _lora_ctx(lora_pool, shard_ids,
                                         lora_tables)
                        first = jnp.where(use_host, override,
                                          prev_toks[last_t, prev_col])
                        live0 = jnp.ones(use_host.shape, bool)

                        def step(carry, xs):
                            cur, live, kp, vp = carry
                            (ids_d, pos, slots, rseq, rctx, uc, temp,
                             key) = xs
                            ids = jnp.where(uc, cur, ids_d)
                            slots = jnp.where(live, slots, ms_scratch)
                            logits, kp, vp = dec._ragged_logits(
                                weights, kp, vp, ids, pos, slots,
                                rseq, rctx, tables, lora=lctx)
                            nxt = self._sample(logits, temp, key)
                            nxt = jnp.where(live, nxt, cur)
                            live = live & (nxt != eos_ids)
                            return (nxt, live, kp, vp), nxt

                        (_, _, k, v), toks = jax.lax.scan(
                            step, (first, live0, k, v),
                            (ids_all, pos_all, slots_all, rseq_all,
                             rctx_all, use_carry, temps_all, keys))
                        return toks, k, v          # [k*T, W]

                    def ragged_ms_lora_chunk_rich(weights, k, v,
                                                  lora_pool, shard_ids,
                                                  lora_tables,
                                                  prev_toks, last_t,
                                                  prev_col, use_host,
                                                  override, ids_all,
                                                  pos_all, slots_all,
                                                  rseq_all, rctx_all,
                                                  use_carry, tables,
                                                  temps_all, keys,
                                                  eos_ids, top_ks_all,
                                                  top_ps_all, reps_all,
                                                  seen, upd, allowed):
                        """ragged_ms_chunk_rich with per-row LoRA
                        deltas."""
                        lctx = _lora_ctx(lora_pool, shard_ids,
                                         lora_tables)
                        first = jnp.where(use_host, override,
                                          prev_toks[last_t, prev_col])
                        live0 = jnp.ones(use_host.shape, bool)
                        w = use_host.shape[0]

                        def step(carry, xs):
                            cur, live, kp, vp, seen_c = carry
                            (ids_d, pos, slots, rseq, rctx, uc, temp,
                             key, tks, tps, rp) = xs
                            ids = jnp.where(uc, cur, ids_d)
                            slots = jnp.where(live, slots, ms_scratch)
                            logits, kp, vp = dec._ragged_logits(
                                weights, kp, vp, ids, pos, slots,
                                rseq, rctx, tables, lora=lctx)
                            nxt = self._sample_rich(logits, temp, key,
                                                    tks, tps, rp,
                                                    seen_c, allowed)
                            nxt = jnp.where(live, nxt, cur)
                            rows = jnp.arange(w)
                            seen_c = seen_c.at[rows, nxt].set(
                                seen_c[rows, nxt] | (upd & live))
                            live = live & (nxt != eos_ids)
                            return (nxt, live, kp, vp, seen_c), nxt

                        (_, _, k, v, _), toks = jax.lax.scan(
                            step, (first, live0, k, v, seen),
                            (ids_all, pos_all, slots_all, rseq_all,
                             rctx_all, use_carry, temps_all, keys,
                             top_ks_all, top_ps_all, reps_all))
                        return toks, k, v          # [k*T, W]

                    if self.tp > 1:
                        self._ragged_ms_lora_j = jax.jit(
                            dec.tp_wrap(ragged_ms_lora_chunk,
                                        n_extra=16, lora_pool=True),
                            donate_argnums=(1, 2))
                        self._ragged_ms_lora_rich_j = jax.jit(
                            dec.tp_wrap(ragged_ms_lora_chunk_rich,
                                        n_extra=22, lora_pool=True),
                            donate_argnums=(1, 2))
                    else:
                        self._ragged_ms_lora_j = jax.jit(
                            ragged_ms_lora_chunk, donate_argnums=(1, 2))
                        self._ragged_ms_lora_rich_j = jax.jit(
                            ragged_ms_lora_chunk_rich,
                            donate_argnums=(1, 2))

        # -- program observatory: register every family (ISSUE 14) ----------
        # the registration order fixes the family names compile spans,
        # attribution histograms and trace_report tables use; `info`
        # carries the decoder's build fingerprint so a compile record
        # says WHICH decoder build it belongs to
        info = dict(getattr(dec, "program_build_info", {}) or {})
        info["tp"] = self.tp
        for fam, fn in self._program_families():
            self.compile_watch.register(fam, fn, **info)
        # which attention implementation each program family compiles
        # to, decided once from the pool geometry and the backend — a
        # pool the chip's compiler refuses the kernel for (head_dim
        # 64, int8 KV) serves through the jnp reference, and says so
        paged = paged_attention_impl(
            dec.head_dim, dec.cache.block_size,
            self.kv_quant is not None)

        def impl(fam):
            if fam.startswith(("decode", "ragged", "spec")):
                return paged
            if fam in ("prefill", "prefill_mid0"):
                return "flash_attention"
            return "dense prefix+suffix (jnp)"

        self.attention_impls = {
            fam: impl(fam) for fam, _ in self._program_families()
            if fam != "merge"}
        _log.log(
            logging.WARNING if jax.default_backend() == "tpu"
            and paged != "pallas" else logging.INFO,
            "ServingEngine attention per program family: %s",
            ", ".join(f"{f}={i}"
                      for f, i in self.attention_impls.items()))

    def _program_families(self):
        """(family name, jitted callable) for every serving program
        this engine can dispatch — the CompileWatch registration set
        AND the warmup_programs grid's family list."""
        fams = [("prefill", self._prefill_j),
                ("prefill_prefix", self._prefill_prefix_j),
                ("decode", self._decode_j),
                ("decode_rich", self._decode_rich_j),
                ("merge", self._merge_first_j)]
        if self._can_recompute:
            fams += [("prefill_mid", self._prefill_mid_j),
                     ("prefill_mid0", self._prefill_mid0_j)]
        if self.ragged:
            fams += [("ragged", self._ragged_j),
                     ("ragged_rich", self._ragged_rich_j)]
        if self.lora is not None:
            fams += [("ragged_lora", self._ragged_lora_j),
                     ("ragged_lora_rich", self._ragged_lora_rich_j)]
        if self.multi_step > 1:
            fams += [("ragged_ms", self._ragged_ms_j),
                     ("ragged_ms_rich", self._ragged_ms_rich_j)]
            if self.lora is not None:
                fams += [("ragged_ms_lora", self._ragged_ms_lora_j),
                         ("ragged_ms_lora_rich",
                          self._ragged_ms_lora_rich_j)]
        if self.spec is not None:
            fams.append(("spec", self._spec_j))
            if self.lora is not None:
                fams.append(("spec_lora", self._spec_lora_j))
        return fams

    @jax.named_scope("sample")
    def _sample(self, logits, temp, key):
        """In-program sampling: per-slot temperature (<=0 → greedy),
        engine-static top_k."""
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if self.top_k > 0:
            kth = jax.lax.top_k(logits, self.top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -1e30, logits)
        t = jnp.maximum(temp, 1e-6)[:, None]
        sampled = jax.random.categorical(
            key, logits / t, axis=-1).astype(jnp.int32)
        return jnp.where(temp > 0.0, sampled, greedy)

    @jax.named_scope("sample")
    def _sample_rich(self, logits, temp, key, top_ks, top_ps, rep,
                     seen, allowed=None):
        """Per-request sampling, all mask-based so one compiled program
        serves every parameter combination (models/generation.py:26-46
        semantics): repetition penalty over the seen mask, per-slot
        top_k via the k-th order statistic of the sorted logits,
        per-slot top_p nucleus over the tempered distribution.
        logits [b, V] f32; temp/top_ps/rep [b] f32; top_ks [b] i32;
        seen [b, V] bool; allowed [b, V] bool (the structured-decoding
        vocab restriction — applied BEFORE the greedy argmax and the
        filters, so constrained greedy is the argmax over the masked
        logits and sampling renormalizes inside the mask; an all-True
        row is the bitwise identity)."""
        v = logits.shape[-1]
        logits = logits.astype(jnp.float32)
        # repetition penalty (HF semantics: shrink positive logits,
        # amplify negative ones, only for already-seen tokens)
        pen = jnp.where(logits > 0, logits / rep[:, None],
                        logits * rep[:, None])
        logits = jnp.where(seen & (rep != 1.0)[:, None], pen, logits)
        if allowed is not None:
            logits = jnp.where(allowed, logits, -1e30)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        lt = logits / jnp.maximum(temp, 1e-6)[:, None]
        # ONE descending sort serves both filters
        sorted_l = jnp.sort(lt, axis=-1)[..., ::-1]         # [b, V]
        # per-slot top_k: k-th largest value as the cutoff
        k_idx = jnp.clip(top_ks - 1, 0, v - 1)
        kth = jnp.take_along_axis(sorted_l, k_idx[:, None], axis=1)
        lt = jnp.where((top_ks > 0)[:, None] & (lt < kth), -1e30, lt)
        # per-slot top_p over the top_k-FILTERED distribution (the
        # generation.py order: top_k first, then nucleus). The filtered
        # sorted array is just the sorted prefix with ranks >= k masked,
        # so the single sort above still serves.
        rank = jnp.arange(v)[None, :]
        sorted_k = jnp.where(
            (top_ks > 0)[:, None] & (rank >= top_ks[:, None]),
            -1e30, sorted_l)
        probs = jax.nn.softmax(sorted_k, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cutoff = cum - probs > top_ps[:, None]
        pth = jnp.where(cutoff, jnp.inf, sorted_k).min(
            axis=-1, keepdims=True)
        lt = jnp.where((top_ps < 1.0)[:, None] & (lt < pth), -1e30, lt)
        sampled = jax.random.categorical(key, lt, axis=-1) \
            .astype(jnp.int32)
        return jnp.where(temp > 0.0, sampled, greedy)

    def _next_key(self):
        self._key, k = jax.random.split(self._key)
        return k

    # -- telemetry (ISSUE 12) ------------------------------------------------
    # reservoir capacity for the finished-request ITL aggregation: big
    # enough that every existing test/bench workload stays EXACT (they
    # emit far fewer samples), small enough to bound unbounded runs
    ITL_RESERVOIR_K = 4096

    def set_telemetry(self, tracer, replica_id: int = 0):
        """Attach (tracer) or detach (None) serving telemetry. The
        tracer is shared down into the KV pool and the adapter registry
        so cache and adapter events ride the same flight recorder;
        ``replica_id`` becomes the pid every record of this engine
        carries (the fleet Router sets it to the replica index)."""
        self.tracer = tracer
        self.replica_id = int(replica_id)
        cache = self.dec.cache
        cache.tracer = tracer
        cache.trace_pid = self.replica_id
        if self.lora is not None:
            self.lora.tracer = tracer
            self.lora.trace_pid = self.replica_id
        # the compile watch shares the tracer's registry (compile
        # spans + compile.* counters land beside everything else);
        # without a tracer it keeps its own registry so sealed-set
        # detection still works untraced
        self.compile_watch.bind(tracer, pid=self.replica_id)

    def _profile_metrics(self):
        """Registry the sampled-attribution histograms feed: the
        tracer's when attached, else a private one (profiling without
        a tracer still measures — the engine just owns the registry)."""
        if self.tracer is not None:
            return self.tracer.metrics
        if self._prof_metrics is None:
            from ..utils.telemetry import MetricsRegistry
            self._prof_metrics = MetricsRegistry()
        return self._prof_metrics

    def _prof_due(self) -> bool:
        """Deterministic every-Nth sampling with a seeded start phase
        (profile_seed): identical runs fence identical dispatches."""
        if not self._prof_n:
            return False
        self._prof_countdown -= 1
        if self._prof_countdown > 0:
            return False
        self._prof_countdown = self._prof_n
        return True

    def _slo_attrs(self, req: Request) -> dict:
        return {"adapter_id": req.sampling.adapter_id,
                "priority": req.sampling.priority}

    def _slo_ttft(self, req: Request, now: float):
        """Feed the request's TTFT into the SLO windows (call sites
        guard on self._slo; all three first-token paths route here)."""
        self._slo.observe("ttft", now - req.t_submit,
                          self._slo_attrs(req), now=now)

    def _mark_first_token(self, req: Request, now: float):
        """First-token bookkeeping shared by the dense/ragged/spec
        prefill-final collection paths — first LIFE only: a
        preemption-recompute re-entry is not a first token and must
        not overwrite the true ttft_s or feed an inflated sample into
        the SLO windows."""
        if req.t_first_token is None:
            req.t_first_token = now
            if self._slo is not None:
                self._slo_ttft(req, now)
        req.t_last_emit = now

    def _prof_record(self, kind: str, fn, host_s: float, queue_s: float,
                     execute_s: float):
        """Record one sampled dispatch attribution: host-schedule
        (since the previous device call ended — admission + schedule
        building), dispatch-queue (draining previously enqueued work)
        and device-execute (this program's own wall), overall and per
        program family."""
        family = self.compile_watch.family_of(fn) \
            or kind.split(":", 1)[-1]
        m = self._profile_metrics()
        m.histogram("profile.host_schedule_s").observe(max(0.0, host_s))
        m.histogram("profile.dispatch_queue_s").observe(
            max(0.0, queue_s))
        m.histogram("profile.device_execute_s").observe(
            max(0.0, execute_s))
        m.histogram(f"profile.device_execute_s.{family}").observe(
            max(0.0, execute_s))
        self.profiled_dispatches += 1
        if self.tracer is not None:
            self.tracer.event(
                "profile_sample", pid=self.replica_id, family=family,
                kind=kind, host_s=round(host_s, 6),
                queue_s=round(queue_s, 6),
                execute_s=round(execute_s, 6))

    def _trace_running(self, req: Request, now: float):
        """Close the current life's prefill span at the prefilling →
        running transition (call sites guard on self.tracer)."""
        if req.trace_id is None:
            return
        t0 = req.t_life or req.t_admit or now
        self.tracer.span(
            "prefill", req.trace_id, t0, now, pid=self.replica_id,
            epoch=req.epoch, n_cached=int(req.n_cached),
            recompute=bool(req.resume), dispatch=self._seq_cur)
        req.t_run = now

    def _trace_life_end(self, req: Request, reason: str, now: float):
        """Close whatever phase span the current life was in — decode
        for a running request, prefill (interrupted) for a prefilling
        one, queued for one that never got a slot — and reset the
        per-life markers (call sites guard on self.tracer)."""
        if req.trace_id is None:
            return
        tr = self.tracer
        if req.t_run is not None:
            tr.span("decode", req.trace_id, req.t_run, now,
                    pid=self.replica_id, epoch=req.epoch, reason=reason,
                    tokens=len(req.out_tokens), dispatch=self._seq_cur)
        elif req.t_life:
            tr.span("prefill", req.trace_id, req.t_life, now,
                    pid=self.replica_id, epoch=req.epoch, reason=reason,
                    interrupted=True)
        elif req.t_queued:
            tr.span("queued", req.trace_id, req.t_queued, now,
                    pid=self.replica_id, reason=reason)
        if req.t_wait is not None:
            tr.span("splice_wait", req.trace_id, req.t_wait, now,
                    pid=self.replica_id, reason=reason)
        req.t_run = None
        req.t_life = 0.0
        req.t_wait = None

    # -- phases of a step ----------------------------------------------------
    def _phase(self, name: str, **attrs) -> _Phase:
        return _Phase(self, name, attrs)

    def _fetch(self, phase: str, kind: str, fn, arg, ch=None):
        """The blocking fetch of one collection, timed as ``phase``:
        (result, None), or (None, the _DispatchFailed that survived the
        retries). ``ch``: the in-flight entry waited for."""
        attrs = {}
        if ch is not None:
            self._seq_cur = attrs["dispatch"] = ch["seq"]
        with self._phase(phase, **attrs):
            try:
                return self._device_call(kind, fn, arg), None
            except _DispatchFailed as e:
                return None, e

    # -- fault tolerance -----------------------------------------------------
    def _device_call(self, kind: str, fn, *args):
        """Every device dispatch/fetch routes through here: the chaos
        injection point plus bounded retry with exponential backoff.
        A transient error (injected or a flaky device/link) re-invokes
        the SAME call — args unchanged, PRNG key already baked in, so a
        successful retry is token-identical to a clean first try.
        Allocator exhaustion passes straight through (it is handled by
        preemption, not retry); anything else that survives the retry
        budget surfaces as _DispatchFailed for the call site to turn
        into structured per-request failures.

        Caveat: a REAL device error raised after the runtime consumed
        a donated pool buffer can leave cache.k/v unusable — the engine
        then fails subsequent requests too, but never raises out of
        step(). The chaos harness always injects BEFORE the underlying
        call, so injected faults are guaranteed retry-safe."""
        attempt = 0
        dispatch = kind.startswith("dispatch:")
        # sampled dispatch-time attribution (ISSUE 14): decided ONCE
        # per logical call (not per retry) so the seeded cadence is
        # schedule-stable; the fences run on the attempt that succeeds
        prof = dispatch and self._prof_due()
        while True:
            try:
                if self.chaos is not None:
                    self.chaos.before_call(self, kind)
                if prof:
                    tq0 = time.perf_counter()
                    host_s = tq0 - self._prof_mark
                    prev = (self._inflight[-1]["toks"]
                            if self._inflight else None)
                    if prev is not None:
                        # drain the device queue so the post-dispatch
                        # fence times THIS program, not its backlog —
                        # the sampled profiling mode's designed sync
                        jax.block_until_ready(prev)  # flightcheck: disable=FC301
                    tq1 = time.perf_counter()
                t0 = time.perf_counter() if dispatch else 0.0
                out = fn(*args)
                t1 = time.perf_counter() if dispatch else 0.0
                if prof:
                    # the sampled fence: device-execute wall of this
                    # program alone (queue drained above). Values are
                    # unchanged — block_until_ready never rewrites —
                    # so tokens stay bitwise identical, sampled or not
                    jax.block_until_ready(out)  # flightcheck: disable=FC301
                    self._prof_record(kind, fn, host_s, tq1 - tq0,
                                      time.perf_counter() - t1)
                    prof = False
                if dispatch:
                    n_new, n_unexp = self.compile_watch.observe(
                        fn, t0, t1, args)
                    if n_new:
                        self.program_compiles += n_new
                    if n_unexp:
                        self.unexpected_recompiles += n_unexp
                    # every successful device-program launch (prefill /
                    # decode / merge / ragged) — the denominator of
                    # stats()["tokens_per_dispatch"]
                    self.device_dispatches += 1
                    self._dispatch_seq += 1
                    self._seq_cur = self._dispatch_seq
                if self._prof_n:
                    self._prof_mark = time.perf_counter()
                return out
            except KVCacheExhausted:
                raise
            except Exception as e:          # noqa: BLE001 — fault wall
                if attempt >= self.max_dispatch_retries:
                    self.dispatch_exhaustions += 1
                    if self.tracer is not None:
                        self.tracer.event(
                            "dispatch_exhausted", pid=self.replica_id,
                            kind=kind, error=type(e).__name__)
                    raise _DispatchFailed(kind, e) from e
                attempt += 1
                self.retries += 1
                if self.tracer is not None:
                    self.tracer.event("retry", pid=self.replica_id,
                                      kind=kind, attempt=attempt)
                if self.retry_backoff_s > 0:
                    time.sleep(self.retry_backoff_s
                               * (2 ** (attempt - 1)))

    def cancel(self, req_id: int) -> bool:
        """Explicitly abort a request in ANY live state: queued (just
        dequeued), prefilling (allocation unwound — splice-pending
        hashes invalidated, dependent readers restarted, blocks freed)
        or running (partial tokens kept; pages freed once no in-flight
        chunk references them). Returns False if the request is already
        terminal; raises KeyError for an unknown id."""
        req = self._find_request(req_id)
        if req is None:
            raise KeyError(f"unknown req_id {req_id}")
        if req.state in ("done", "aborted", "failed"):
            return False
        self._abort_request(req, "cancelled")
        return True

    def _find_request(self, req_id: int) -> Optional[Request]:
        if req_id in self._done:
            return self._done[req_id]
        for r in self._slots:
            if r is not None and r.req_id == req_id:
                return r
        for r in self._queue:
            if r.req_id == req_id:
                return r
        return None

    def _enforce_deadlines(self):
        """Abort every live request past its wall-clock deadline (the
        terminal state is ABORTED with error='deadline...'; partial
        tokens are kept — a caller that can use a truncated answer
        still gets one)."""
        now = time.perf_counter()
        expired = [r for r in list(self._queue)
                   + [s for s in self._slots if s is not None]
                   if r.deadline_at is not None and now > r.deadline_at]
        for req in expired:
            self.deadline_misses += 1
            self._abort_request(
                req, f"deadline exceeded "
                     f"({req.sampling.deadline_s:.3f}s budget)")

    def _estimate_completion_s(self, sp: SamplingParams
                               ) -> Optional[float]:
        """Admission-time completion estimate for overload shedding:
        backlog tokens (queued + running remainders + the candidate's
        own budget) over the engine's measured aggregate token rate.
        None until the engine has produced enough traffic to have a
        rate — cold engines never shed on deadline math."""
        busy = self.time_prefill_s + self.time_stall_s + self.time_host_s
        if self.generated_tokens < 8 or busy <= 0:
            return None
        rate = self.generated_tokens / busy
        backlog = sum(r.sampling.max_new_tokens - len(r.out_tokens)
                      for r in self._queue)
        backlog += sum(r.sampling.max_new_tokens - len(r.out_tokens)
                       for r in self._slots if r is not None)
        return (backlog + sp.max_new_tokens) / rate

    def _pick_victim(self, exclude=()) -> Optional[Request]:
        """Preemption victim under KV pressure: lowest priority first,
        newest req_id on ties — so the oldest highest-priority request
        always makes progress (no preemption livelock). Running
        requests are preferred victims (their blocks free the most);
        prefilling ones only when no running victim exists."""
        if not self._can_recompute:
            return None
        for states in (("running",), ("prefilling",)):
            cands = [r for r in self._slots
                     if r is not None and r.state in states
                     and r not in exclude]
            if cands:
                return max(cands, key=lambda r: (-r.sampling.priority,
                                                 r.req_id))
        return None

    def _preempt(self, victim: Request):
        """Preemption-with-recompute: evict `victim` from its slot,
        free its blocks back to the pool NOW (safe: any in-flight chunk
        touching them was dispatched earlier, and device program order
        runs it before any later program that could reuse the pages;
        collection drops the victim's in-flight tokens via the epoch
        guard), and re-enqueue it at the queue front. A RUNNING victim
        resumes by re-prefilling prompt ++ generated history through
        the no-sample chunk programs — full prompt blocks usually park
        in the prefix-cache LRU at free and splice straight back in,
        so recompute cost is near zero on hits. A PREFILLING victim
        restarts its prefill from scratch."""
        self.preemptions += 1
        if self.tracer is not None and victim.trace_id is not None:
            self.tracer.event(
                "preempt", trace=victim.trace_id, pid=self.replica_id,
                state=victim.state, tokens=len(victim.out_tokens),
                priority=victim.sampling.priority)
        self._evict_to_queue(victim)
        self._requeue_front([victim])

    def _evict_to_queue(self, req: Request):
        """Evict a live slotted request back to a fresh queued life:
        bump the epoch (collection drops the old life's in-flight
        tokens), vacate the slot, unwind/free the old allocation, and
        reset all per-life prefill progress. The unwind runs while the
        old coverage (n_cached/prefill_sent/deps) is still intact —
        a RUNNING request's fully-dispatched prefill lets reader deps
        prune as met BEFORE the reset below could spuriously re-arm
        them against the next life. The free is always IMMEDIATE (safe
        by device program order: every in-flight chunk touching the
        pages was dispatched earlier) — deferring it to collection
        while the request re-enters the queue would let the next
        _admit re-allocate its seq before the free lands and raise out
        of step(). The caller requeues."""
        if self.tracer is not None and req.trace_id is not None:
            now = time.perf_counter()
            self._trace_life_end(req, "evict", now)
            req.t_queued = now      # the requeued life's queued span
        req.epoch += 1
        si = req.slot
        if si is not None:
            self._slots[si] = None
            self._fresh_slots.discard(si)
        req.slot = None
        # adapter pin travels with the slot: the evicted life's pages
        # park (evictable — "an adapter eviction preempts like a KV
        # OOM"); re-admission re-acquires, reviving or refaulting
        self._lora_release(req)
        if req.state == "prefilling":
            self._unwind_alloc(req, immediate=True)
        else:
            self._restart_dependent_readers(req)
            self.dec.cache.free(req.req_id)
        req.resume = bool(req.out_tokens)
        req.state = "queued"
        req.planned = len(req.out_tokens)
        req.n_cached = 0
        req.prefill_sent = 0
        req.deps = []
        req.pending_blocks = []
        req.ctx = None

    def _extend_with_preempt(self, req: Request, exclude=()) -> int:
        """cache.extend with pressure relief: on exhaustion, preempt
        the policy victim (lowest priority first, newest on ties —
        see _pick_victim; no age constraint relative to `req` itself)
        and retry. `req` stays in the victim pool — when IT is the
        chosen victim the exhaustion propagates and the caller FAILS
        `req` (both callers, _dispatch_mid and _dispatch_final,
        convert it to a terminal failed state)."""
        while True:
            try:
                return self.dec.cache.extend(req.req_id)
            except KVCacheExhausted:
                victim = self._pick_victim(exclude=tuple(exclude))
                if victim is None or victim is req:
                    raise
                self._preempt(victim)

    def _requeue_front(self, reqs: Sequence[Request]):
        """Put preempted/restarted requests back into the queue in
        global req_id order. Arrivals enter the queue in req_id order,
        so re-sorting the whole queue keeps FIFO fairness while placing
        every evicted request ahead of anything that arrived after it —
        including requests requeued by EARLIER calls (a blind
        front-prepend would let a newer victim jump an older restarted
        request and starve it under sustained pressure)."""
        if not reqs:
            return
        merged = sorted(list(self._queue) + list(reqs),
                        key=lambda r: r.req_id)
        self._queue.clear()
        self._queue.extend(merged)

    def _unwind_alloc(self, req: Request, immediate: bool = False):
        """Safely unwind a PREFILLING request's allocation:
        1. invalidate hash registrations of its own full prefill blocks
           whose covering chunk was never dispatched (their registered
           content will never exist — a later splice would read junk);
        2. drop its splice-pending writer entries;
        3. restart any reader still waiting on those unwritten blocks
           (the reader spliced physical blocks this request will now
           never write — its allocation is unwound recursively and it
           re-enters the queue);
        4. free the blocks (immediately for preemption — the caller
           needs them NOW; otherwise after the newest in-flight chunk,
           like _retire)."""
        cache = self.dec.cache
        bs = cache.block_size
        covered = req.n_cached + req.prefill_sent
        try:
            table = cache.seq_blocks(req.req_id)
        except KeyError:
            table = None
        if table is not None:
            own_uncovered = [
                table[j]
                for j in range(req.n_cached // bs,
                               len(req.prefill_tokens) // bs)
                if (j + 1) * bs > covered and j < len(table)]
            cache.unregister_block_hashes(own_uncovered)
        self._clear_pending_writes(req)
        self._restart_dependent_readers(req)
        if table is not None:
            if immediate or not self._inflight:
                cache.free(req.req_id)
            else:
                self._inflight[-1]["free_after"].append(req.req_id)

    def _restart_dependent_readers(self, writer: Request):
        """Resolve every splice dependency on `writer` against its
        CURRENT dispatch coverage, BEFORE that coverage is rolled back
        by preemption/unwind: met deps reference chunks that were
        really dispatched and will execute regardless of what happens
        to the writer now — they are PRUNED here (left in place, a met
        dep would spuriously re-arm against the writer's next life,
        whose prefill_sent restarts at 0 with different blocks and a
        possibly shorter suffix — the reader would stall forever).
        Readers with UNMET deps spliced blocks the writer will now
        never write; they restart from scratch."""
        for r in self._slots:
            if r is not None and r.deps:
                r.deps = [(w, need) for w, need in r.deps
                          if not (w is writer
                                  and writer.prefill_sent >= need)]
        readers = [r for r in self._slots
                   if r is not None and r.state == "prefilling"
                   and any(w is writer for w, need in r.deps)]
        restarted = []
        for r in readers:
            # the recursive unwind below may already have restarted a
            # later snapshot entry (a reader depending on BOTH this
            # writer and r) — evicting it twice would double-enqueue it
            if r.state != "prefilling":
                continue
            self._evict_to_queue(r)      # recursive: r may have readers
            restarted.append(r)
        self._requeue_front(restarted)

    def _abort_request(self, req: Request, msg: str):
        self.aborted += 1
        self._finalize(req, "aborted", msg)

    def _fail_request(self, req: Request, msg: str):
        self.failed += 1
        self._finalize(req, "failed", msg)

    def _finalize(self, req: Request, state: str, msg: str):
        """Move a live request to a terminal fault state, unwinding
        whatever stage it was in. Partial tokens are kept; `error`
        records why."""
        if req.state == "queued":
            try:
                self._queue.remove(req)
            except ValueError:
                pass
        else:
            si = req.slot
            if si is not None:
                self._slots[si] = None
                self._fresh_slots.discard(si)
            req.slot = None
            req.epoch += 1     # in-flight chunks must drop its tokens
            self._lora_release(req)
            if req.state == "prefilling":
                self._unwind_alloc(req)
            elif req.req_id in self.dec.cache._tables:
                # running: pages freed after the newest in-flight chunk
                # (it was dispatched assuming continuation), like
                # _retire
                if self._inflight:
                    self._inflight[-1]["free_after"].append(req.req_id)
                else:
                    self.dec.cache.free(req.req_id)
        req.state = state
        req.error = msg
        req.t_done = time.perf_counter()
        if self.tracer is not None and req.trace_id is not None:
            self._trace_life_end(req, state, req.t_done)
            if not req.trace_keep_open:
                self.tracer.end_request(
                    req.trace_id, state, replica=self.replica_id,
                    error=msg)
        self._done[req.req_id] = req

    def debug_dump(self) -> str:
        """One human-readable snapshot of the scheduler — per-request
        states, queue/pipeline depth, robustness counters and cache
        occupancy. The watchdog appends this to its hang report."""
        cache = self.dec.cache
        lines = ["serving engine state:"]
        for si, r in enumerate(self._slots):
            if r is None:
                lines.append(f"  slot {si}: idle")
            else:
                lines.append(
                    f"  slot {si}: req {r.req_id} state={r.state} "
                    f"out={len(r.out_tokens)}/{r.sampling.max_new_tokens}"
                    f" planned={r.planned} prefill={r.prefill_sent}/"
                    f"{r.suffix_len} epoch={r.epoch} resume={r.resume}")
        lines.append(f"  queue depth={len(self._queue)} ids="
                     f"{[r.req_id for r in self._queue][:16]}")
        lines.append(f"  inflight={len(self._inflight)} "
                     f"finished={len(self._done)}")
        lines.append(
            f"  counters: preemptions={self.preemptions} "
            f"retries={self.retries} aborted={self.aborted} "
            f"failed={self.failed} deadline_misses={self.deadline_misses}"
            f" shed={self.shed_requests} "
            f"recompute_tokens={self.recompute_tokens}")
        lines.append(
            f"  cache: free_blocks={cache.free_blocks} "
            f"cached_blocks={cache.cached_blocks} "
            f"referenced={len(cache._ref)} of {cache.num_blocks}")
        return "\n".join(lines) + "\n"

    # -- public API ----------------------------------------------------------
    def _validate_new_request(self, prompt, sp: SamplingParams):
        """Shared admission validation (add_request and the fleet
        migration path adopt_request): prompt normalization, bucket and
        pool-geometry checks, adapter registration, allowed-tokens mask
        normalization. Returns (prompt, allowed_mask). Raises on
        impossible geometry — validation, NOT shedding (the overload
        checks live in add_request only: a migrated request was already
        admitted to the fleet once and must not be shed at drain)."""
        prompt = _normalize_prompt(prompt)
        _bucket_for(int(prompt.size), self.buckets)  # validates length
        cache = self.dec.cache
        need = -(-(int(prompt.size) + sp.max_new_tokens)
                 // cache.block_size)
        # a tenant request must fit its KV *plus* its adapter's pages
        # (both come out of the same pool) — reject impossible
        # geometry at the door, like oversized prompts
        lora_pages = 0
        if sp.adapter_id is not None:
            if self.lora is None:
                raise ValueError(
                    f"adapter_id={sp.adapter_id!r} but the engine has "
                    f"no AdapterRegistry (pass lora= to ServingEngine)")
            if not self.lora.is_registered(sp.adapter_id):
                raise KeyError(
                    f"unknown adapter {sp.adapter_id!r} — register it "
                    f"before submitting requests")
            lora_pages = self.lora.n_pages()
        if need + lora_pages > cache.num_blocks - 1:  # -1: scratch page
            raise ValueError(
                f"request needs {need} KV pages"
                + (f" + {lora_pages} adapter pages" if lora_pages
                   else "")
                + f" but the pool only has {cache.num_blocks - 1}; "
                "shrink max_new_tokens/prompt or grow num_blocks")
        allowed_mask = None
        if sp.allowed_tokens is not None:
            allowed_mask = self._normalize_allowed(
                sp.allowed_tokens, self.dec.cfg.vocab_size)
        return prompt, allowed_mask

    def add_request(self, prompt, sampling: Optional[SamplingParams] = None
                    ) -> int:
        """Queue a prompt ([len] ids; list/np/Tensor). Returns req_id."""
        sp = sampling or SamplingParams()
        prompt, allowed_mask = self._validate_new_request(prompt, sp)
        # overload shedding: reject at the door what cannot be served —
        # a hard queue-depth cap, and (for deadline'd requests, once the
        # engine has a measured token rate) a backlog/deadline estimate
        if self.max_queue_depth is not None and \
                len(self._queue) >= self.max_queue_depth:
            self.shed_requests += 1
            if self.tracer is not None:
                self.tracer.event("shed", pid=self.replica_id,
                                  reason="queue_depth")
            raise EngineOverloaded(
                f"queue depth {len(self._queue)} at the "
                f"max_queue_depth={self.max_queue_depth} cap")
        if sp.deadline_s is not None:
            est = self._estimate_completion_s(sp)
            if est is not None and est > sp.deadline_s:
                self.shed_requests += 1
                if self.tracer is not None:
                    self.tracer.event("shed", pid=self.replica_id,
                                      reason="deadline_estimate")
                raise EngineOverloaded(
                    f"estimated completion {est:.3f}s exceeds the "
                    f"{sp.deadline_s:.3f}s deadline "
                    f"(backlog {len(self._queue)} queued)")
        rid = next(self._ids)
        req = Request(rid, prompt, sp, t_submit=time.perf_counter())
        req.allowed_mask = allowed_mask
        req.t_queued = req.t_submit
        if self.tracer is not None:
            req.trace_id = self.tracer.begin_request(
                rid, tenant=sp.adapter_id, replica=self.replica_id,
                prompt_len=int(prompt.size),
                max_new_tokens=sp.max_new_tokens)
        self._queue.append(req)
        return rid

    def adopt_request(self, prompt, sampling: Optional[SamplingParams]
                      = None, out_tokens: Sequence[int] = (),
                      t_submit: Optional[float] = None,
                      trace_id: Optional[int] = None) -> int:
        """Admit a request that already ran (partially) on ANOTHER
        engine — the fleet Router's replica-failover migration path
        (inference/fleet.py). The generated history re-enters this
        engine's pool through the preemption-recompute machinery
        (resume=True): the prefill reads prompt ++ out_tokens[:-1]
        through the NO-SAMPLE chunk programs — no PRNG key is drawn,
        the engine's key stream is untouched — and decode resumes from
        out_tokens[-1], so greedy outputs are token-identical across
        the migration. Overload shedding is BYPASSED (the fleet already
        admitted this request once; shedding a drain would drop it) —
        pool-geometry validation still applies. ``t_submit`` preserves
        the original submit time so deadlines keep their meaning on the
        new engine. A history that already satisfies the stop condition
        (budget spent / trailing EOS) completes immediately; an engine
        without the chunk programs drops the history and re-runs from
        the prompt (still greedy-identical, just more recompute).
        ``trace_id`` continues an existing telemetry span (the Router
        passes the migrating request's id, so the whole lifecycle stays
        ONE continuous span across replicas; None opens a fresh one
        when a tracer is attached)."""
        sp = sampling or SamplingParams()
        prompt, allowed_mask = self._validate_new_request(prompt, sp)
        rid = next(self._ids)
        req = Request(rid, prompt, sp,
                      t_submit=(time.perf_counter() if t_submit is None
                                else float(t_submit)))
        req.allowed_mask = allowed_mask
        req.t_queued = time.perf_counter()
        if self.tracer is not None:
            req.trace_id = (int(trace_id) if trace_id is not None
                            else self.tracer.begin_request(
                                rid, tenant=sp.adapter_id,
                                replica=self.replica_id,
                                prompt_len=int(prompt.size)))
            self.tracer.event(
                "adopt", trace=req.trace_id, pid=self.replica_id,
                history=len(out_tokens), req_id=rid)
        toks = [int(t) for t in out_tokens]
        if toks and not self._can_recompute:
            # no no-sample chunk programs: the history cannot re-enter
            # the pool without drawing keys — from-scratch re-prefill
            toks = []
        req.out_tokens = toks
        if toks and (len(toks) >= sp.max_new_tokens
                     or (sp.eos_token_id is not None
                         and toks[-1] == sp.eos_token_id)):
            # the migrated history already finished the request — a
            # resume admission would schedule one decode row past the
            # budget before retiring; complete it here instead
            req.out_tokens = toks[:sp.max_new_tokens]
            req.state = "done"
            req.t_done = time.perf_counter()
            if self.tracer is not None:
                self.tracer.end_request(
                    req.trace_id, "done", replica=self.replica_id,
                    tokens=len(req.out_tokens))
            self._done[rid] = req
            return rid
        req.resume = bool(toks)
        req.planned = len(toks)
        self._queue.append(req)
        return rid

    def result(self, req_id: int) -> np.ndarray:
        """Generated tokens (prompt excluded) of a terminal request.
        For aborted/failed requests this is the PARTIAL output produced
        before the fault — check request(req_id).state / .error."""
        req = self._done[req_id]
        return np.asarray(req.out_tokens, np.int32)

    def request(self, req_id: int) -> Request:
        """The terminal Request record (state is one of done | aborted
        | failed; error says why for the fault states)."""
        return self._done[req_id]

    @property
    def has_work(self) -> bool:
        return (bool(self._queue) or bool(self._inflight)
                or any(r is not None for r in self._slots))

    # -- scheduler -----------------------------------------------------------
    def _admit(self):
        """Claim free batch slots for queued requests. Admission is
        capacity-aware (a request enters only if its whole worst-case
        page demand fits — net of prefix-cache reuse — so a running
        request can never hit pool exhaustion mid-prefill or
        mid-decode) and NON-BLOCKING: it allocates pages and puts the
        request in the "prefilling" state; the actual prefill chunks
        are dispatched by _dispatch_prefill and their results fetched
        at collection time, like decode chunks.

        Prefix caching splices matched blocks at allocation time. A
        matched block may belong to a request that is still mid-prefill
        (its suffix's full prompt blocks register in the hash index at
        allocation, before any write is dispatched): the reader records
        (writer, suffix-tokens-needed) dependencies and its chunks hold
        back until the writer's covering dispatch has been issued —
        on-device program order then guarantees the reader sees the
        writer's pages."""
        cache = self.dec.cache
        for si in range(self.max_b):
            if self._slots[si] is not None:
                continue
            if not self._queue:
                break
            req = self._queue[0]
            # resume (preempted while running): the prefill source is
            # prompt ++ generated history minus the last token — the
            # history re-enters the pool via no-sample chunks and
            # decode resumes from out_tokens[-1]. Fresh admissions
            # prefill the prompt as before.
            if req.resume and req.out_tokens:
                toks = np.concatenate(
                    [req.prompt,
                     np.asarray(req.out_tokens[:-1], np.int32)])
            else:
                toks = req.prompt
            # pages reserved up front: everything (worst_case — a
            # running request can never exhaust the pool) or just the
            # prefill + one decode slot (optimistic — oversubscribes
            # the pool; pressure is relieved by preemption)
            if self.admission == "optimistic":
                total = int(len(toks)) + 1
            else:
                total = int(req.prompt.size) + req.sampling.max_new_tokens
            # adapter fault-in FIRST (ISSUE 10): its pages come out of
            # the same pool the KV allocation below draws from, so the
            # two claims must be ordered and individually unwound — an
            # adapter that cannot fault in waits at the queue head
            # exactly like a KV refusal (preemptions and frees
            # downstream relieve both)
            if req.sampling.adapter_id is not None:
                try:
                    self._lora_acquire(req)
                except KVCacheExhausted:
                    break  # head-of-line: keep FIFO, wait for frees
            if self.prefix_caching:
                try:
                    # one hash walk: the capacity check happens inside
                    # allocate_with_prefix BEFORE any mutation, so a
                    # refusal leaves the pool untouched. The chain is
                    # SALTED with the adapter id: a tenant's blocks
                    # hold its adapter's K/V and must never splice
                    # into another tenant's (or the base model's)
                    # table
                    reused, n_cached = cache.allocate_with_prefix(
                        req.req_id, toks, total,
                        salt=req.sampling.adapter_id)
                except RuntimeError:
                    # keep FIFO; drop the adapter pin taken above (the
                    # adapter stays parked-resident, so the retry next
                    # step is a cheap revive)
                    self._lora_release(req)
                    break
                req.deps = [self._pending_writes[b] for b in reused
                            if b in self._pending_writes]
                # register OUR fresh full prefill blocks as splice-
                # pending until our dispatches cover them
                table = cache.seq_blocks(req.req_id)
                bs = cache.block_size
                n_full = int(len(toks)) // bs
                for j in range(len(reused), n_full):
                    self._pending_writes[table[j]] = \
                        (req, (j + 1) * bs - n_cached)
                    req.pending_blocks.append(table[j])
            else:
                if cache.free_blocks < -(-total // cache.block_size):
                    self._lora_release(req)
                    break
                try:
                    cache.allocate(req.req_id, total)
                except RuntimeError:
                    self._lora_release(req)
                    break
                n_cached = 0
            self._queue.popleft()
            req.ctx = toks if req.resume else None
            req.n_cached = n_cached
            req.state = "prefilling"
            req.slot = si
            now = time.perf_counter()
            if req.t_admit is None:
                req.t_admit = now
            if self.tracer is not None and req.trace_id is not None:
                self.tracer.span(
                    "queued", req.trace_id, req.t_queued or now, now,
                    pid=self.replica_id, epoch=req.epoch,
                    resume=bool(req.resume))
                self.tracer.event(
                    "admitted", trace=req.trace_id,
                    pid=self.replica_id, slot=si,
                    n_cached=int(n_cached), resume=bool(req.resume))
                req.t_life = now
            if req.resume:
                # tokens that must genuinely recompute (past the splice)
                self.recompute_tokens += req.suffix_len
            self._slots[si] = req

    def _deps_ready(self, req: Request) -> bool:
        """True when every splice-pending writer has dispatched the
        chunks covering the blocks `req` spliced. Satisfied entries are
        PRUNED on the spot: a dispatched chunk executes no matter what
        later happens to its writer, but a preempted writer's
        prefill_sent rolls back to 0 — without pruning, a met
        dependency could spuriously re-arm against the writer's next
        life (whose blocks are different anyway)."""
        if req.deps:
            req.deps = [(w, need) for w, need in req.deps
                        if w.prefill_sent < need]
        if not req.deps:
            if req.t_wait is not None:
                # splice-wait over: the reader held its chunks back
                # for this long waiting on the writer's dispatches
                if self.tracer is not None and req.trace_id is not None:
                    self.tracer.span(
                        "splice_wait", req.trace_id, req.t_wait,
                        time.perf_counter(), pid=self.replica_id)
                req.t_wait = None
            return True
        if self.tracer is not None and req.t_wait is None:
            req.t_wait = time.perf_counter()
        return False

    def _clear_pending_writes(self, req: Request):
        for b in req.pending_blocks:
            if self._pending_writes.get(b, (None, 0))[0] is req:
                del self._pending_writes[b]
        req.pending_blocks = []

    def _dispatch_prefill(self):
        """Dispatch prefill work for prefilling slots, oldest request
        first (FIFO completes the earliest prompt soonest, which
        minimizes its TTFT and resolves splice dependencies in
        admission order). While decodes are running the dispatched
        tokens are capped at prefill_budget per step — the bound on
        how much prefill can slot between two decode chunks; an idle
        engine dispatches everything ready. Suffixes longer than
        prefill_chunk go out as width-1 fixed-size chunks (no-sample
        programs); each request's last dispatch is its bucketed,
        sampling "final" — grouped across requests per bucket exactly
        like monolithic admission prefills."""
        pending = sorted((r for r in self._slots
                          if r is not None and r.state == "prefilling"
                          and r.prefill_sent < r.suffix_len),
                         key=lambda r: r.req_id)
        if not pending:
            return
        decoding = any(r is not None and r.state == "running"
                       for r in self._slots)
        budget = self.prefill_budget if (decoding and
                                         self.prefill_budget) else None
        def _is_mid(r):
            # a preemption resume runs EVERY chunk through the
            # no-sample mid program (its "first token" is already
            # known — re-sampling would both corrupt the request and
            # shift the engine's PRNG stream for everyone else)
            if r.resume:
                return True
            return (self.prefill_chunk and
                    r.suffix_len - r.prefill_sent > self.prefill_chunk)

        spent = 0
        while True:
            ready = [r for r in pending
                     if r.state == "prefilling" and r.slot is not None
                     and r.prefill_sent < r.suffix_len
                     and self._deps_ready(r)]
            if not ready:
                return
            # strict FIFO: the OLDEST ready request's next dispatch goes
            # first — a newer long prompt's chunks must never starve an
            # older short request's final
            head = ready[0]
            if _is_mid(head):
                spent += self._dispatch_mid(head)
                if budget is not None and spent >= budget:
                    return
                continue
            # head's remainder fits one dispatch: group every ready
            # same-bucket final with it (equal priority, shared
            # program), closing a sub-group early when it crosses the
            # remaining budget — so at most ~budget + one row's suffix
            # ever slots between two decode chunks, not a whole
            # width-PREFILL_GROUP burst
            bucket = _bucket_for(head.suffix_len - head.prefill_sent,
                                 self.buckets)
            group = [(r.slot, r, r.n_cached + r.prefill_sent)
                     for r in ready if not _is_mid(r)
                     and _bucket_for(r.suffix_len - r.prefill_sent,
                                     self.buckets) == bucket]
            w = min(self.PREFILL_GROUP, self.max_b) \
                if len(group) > 1 else 1
            sub, toks = [], 0
            for row in group:
                if row[1].state != "prefilling" or row[1].slot is None:
                    # an EARLIER sub's (injected) KV exhaustion picked
                    # this row's request as the preemption victim —
                    # its seq is freed and the row is stale; it will
                    # re-enter through the queue
                    continue
                sub.append(row)
                toks += int(row[1].prompt.size) - row[2]
                if len(sub) == w or (budget is not None
                                     and spent + toks >= budget):
                    self._dispatch_final(bucket, sub, w)
                    spent += toks
                    sub, toks = [], 0
                    if budget is not None and spent >= budget:
                        return
            if sub:
                self._dispatch_final(bucket, sub, w)
                spent += toks
                if budget is not None and spent >= budget:
                    return

    # prefill dispatch widths: exactly TWO compile variants per bucket
    # (a variant per group size would compile-storm on bursty arrivals)
    PREFILL_GROUP = 4

    @_phased("engine.prefill_dispatch")
    def _dispatch_mid(self, req: Request) -> int:
        """Dispatch ONE fixed-size no-sample prefill chunk (width 1).
        The chunk prefills at global offset n_cached + prefill_sent
        with everything before it — spliced prefix AND previously
        dispatched chunks — riding along as the prefix page table;
        offsets need not be page-aligned (the attention masks the
        partial last page). A preemption resume's TAIL chunk may be
        shorter than the chunk width: ids are right-padded with zeros
        and the pad K/V aimed at the scratch page (the causal mask
        hides pad keys from real queries, so padding is inert).
        Returns the number of real tokens dispatched (0 when the
        dispatch failed and the request was unwound)."""
        cache = self.dec.cache
        c = self.prefill_chunk or self._recompute_chunk
        toks = req.prefill_tokens
        off = req.n_cached + req.prefill_sent
        take = min(c, req.suffix_len - req.prefill_sent)
        ids = np.zeros((1, c), np.int32)
        ids[0, :take] = toks[off:off + take]
        slots = np.full((1, c), self._scratch_slot, np.int32)
        try:
            for j in range(take):
                slots[0, j] = self._extend_with_preempt(req)
        except KVCacheExhausted as e:
            self._fail_request(req, f"KV pool exhausted mid-prefill "
                                    f"with no preemption victim: {e}")
            return 0
        try:
            if off:
                need = -(-off // cache.block_size)
                width = next(b for b in self._prefix_page_buckets
                             if b >= need)
                ptab = np.full((1, width), self._scratch_block,
                               np.int32)
                pb = cache.seq_blocks(req.req_id)[:need]
                ptab[0, :len(pb)] = pb
                cache.k, cache.v = self._device_call(
                    "dispatch:prefill_mid", self._prefill_mid_j,
                    self.dec.weights, cache.k, cache.v,
                    jnp.asarray(ids), jnp.asarray(slots),
                    jnp.asarray([off], np.int32), jnp.asarray(ptab))
            else:
                cache.k, cache.v = self._device_call(
                    "dispatch:prefill_mid", self._prefill_mid0_j,
                    self.dec.weights, cache.k, cache.v,
                    jnp.asarray(ids), jnp.asarray(slots))
        except _DispatchFailed as e:
            self._fail_request(req, f"prefill dispatch failed after "
                                    f"retries: {e}")
            return 0
        req.prefill_sent += take
        if self.tracer is not None:
            self.tracer.event(
                "dispatch", trace=req.trace_id, pid=self.replica_id,
                kind="prefill_mid", rows=1, tokens=int(take),
                offset=int(off))
        self._ph.set(dispatch=self._dispatch_seq, prefill_tokens=int(take))
        self._inflight.append({"kind": "prefill", "toks": None,
                               "seq": self._dispatch_seq,
                               "group": [], "free_after": []})
        if req.resume and req.prefill_sent >= req.suffix_len:
            self._resume_complete(req)
        return take

    def _resume_complete(self, req: Request):
        """A preemption resume finishes at DISPATCH time — no sampling
        final, no collection barrier: the next decode input is the
        already-emitted out_tokens[-1], supplied from the host exactly
        like a fresh prefill's first token."""
        req.state = "running"
        if self.tracer is not None:
            self._trace_running(req, time.perf_counter())
        self._clear_pending_writes(req)
        si = req.slot
        self._last_tok[si] = req.out_tokens[-1]
        self._fresh_slots.add(si)
        req.planned = len(req.out_tokens)

    @_phased("engine.prefill_dispatch")
    def _dispatch_final(self, bucket: int, group, gp: int):
        """Dispatch one FINAL (first-token-sampling) prefill for rows
        whose remaining suffix fits a single bucketed dispatch —
        either a whole short prompt or the tail of a chunked one.
        `group` rows are (slot, req, off): `off` counts spliced prefix
        plus already-dispatched chunk tokens, so `bucket` is the
        REMAINDER bucket, RoPE positions/slot mappings start at `off`,
        and the covered pages ride along as a scratch-padded prefix
        table. The dispatch is queued; tokens are fetched at
        collection time."""
        cache = self.dec.cache
        vocab = self.dec.cfg.vocab_size
        ids = np.zeros((gp, bucket), np.int32)
        slots = np.full((gp, bucket), self._scratch_slot, np.int32)
        last_idx = np.zeros(gp, np.int32)
        ncv = np.zeros(gp, np.int32)
        ptab = np.full((gp, self._prefix_pages), self._scratch_block,
                       np.int32)
        temps = np.zeros(gp, np.float32)
        top_ks = np.zeros(gp, np.int32)
        top_ps = np.ones(gp, np.float32)
        reps = np.ones(gp, np.float32)
        any_rep = any(req.sampling.repetition_penalty != 1.0
                      for _, req, _ in group)
        seen = np.zeros((gp, vocab), bool) if any_rep else None
        members = [req for _, req, _ in group]
        try:
            for row, (si, req, off) in enumerate(group):
                s = int(req.prompt.size) - off
                ids[row, :s] = req.prompt[off:]
                slots[row, :s] = [
                    self._extend_with_preempt(req, exclude=members)
                    for _ in range(s)]
                last_idx[row] = s - 1
                ncv[row] = off
                if off:
                    pb = cache.seq_blocks(req.req_id)[
                        : -(-off // cache.block_size)]
                    ptab[row, :len(pb)] = pb
                sp = req.sampling
                temps[row] = sp.temperature
                # engine-level top_k is the default where the request
                # does not set its own (None); an explicit 0 disables it
                top_ks[row] = self.top_k if sp.top_k is None \
                    else sp.top_k
                top_ps[row] = sp.top_p
                reps[row] = sp.repetition_penalty
                if sp.repetition_penalty != 1.0:
                    seen[row, req.prompt] = True  # FULL prompt, cached
        except KVCacheExhausted as e:
            # no victim left for the group's suffix slots (only
            # reachable through an injected-fault storm on a
            # worst-case-admitted pool): the group shares one dispatch
            # and its rows are already entangled — fail it whole
            for req in members:
                self._fail_request(
                    req, f"KV pool exhausted building prefill "
                         f"group: {e}")
            return
        seen_dev = jnp.asarray(seen) if any_rep \
            else self._zeros_seen(gp, vocab)
        allowed_dev = self._allowed_operand(
            gp, [(row, req.allowed_mask)
                 for row, (_si, req, _off) in enumerate(group)])
        # the suffix-prefix program pays a per-layer page gather plus
        # dense attention over the (possibly all-masked) prefix columns:
        # only groups with at least one covered prefix take it —
        # cold-start groups keep the plain flash prefill, so disjoint
        # unchunked traffic is unchanged
        try:
            if any(off for _, _, off in group):
                toks, cache.k, cache.v = self._device_call(
                    "dispatch:prefill", self._prefill_prefix_j,
                    self.dec.weights, cache.k, cache.v,
                    jnp.asarray(ids), jnp.asarray(slots),
                    jnp.asarray(last_idx), jnp.asarray(ncv),
                    jnp.asarray(ptab), jnp.asarray(temps),
                    self._next_key(), jnp.asarray(top_ks),
                    jnp.asarray(top_ps), jnp.asarray(reps), seen_dev,
                    allowed_dev)
            else:
                toks, cache.k, cache.v = self._device_call(
                    "dispatch:prefill", self._prefill_j,
                    self.dec.weights, cache.k, cache.v,
                    jnp.asarray(ids), jnp.asarray(slots),
                    jnp.asarray(last_idx), jnp.asarray(temps),
                    self._next_key(), jnp.asarray(top_ks),
                    jnp.asarray(top_ps), jnp.asarray(reps), seen_dev,
                    allowed_dev)
        except _DispatchFailed as e:
            # request mutations happen only after a SUCCESSFUL
            # dispatch, so coverage bookkeeping is still truthful here:
            # unwinding restarts exactly the readers whose spliced
            # blocks will now never be written
            for req in members:
                self._fail_request(
                    req, f"prefill dispatch failed after retries: {e}")
            return
        for si, req, off in group:
            req.prefill_sent = req.suffix_len
            self._clear_pending_writes(req)
        if self.tracer is not None:
            self.tracer.event("dispatch", pid=self.replica_id,
                              kind="prefill", rows=int(gp),
                              bucket=int(bucket))
        self._ph.set(dispatch=self._dispatch_seq)
        self._inflight.append({"kind": "prefill", "toks": toks,
                               "seq": self._dispatch_seq,
                               "group": [(si, req, req.epoch)
                                         for si, req, _ in group],
                               "free_after": []})

    def _prefill_complete(self, toks: np.ndarray, group):
        """Post-fetch bookkeeping for one collected FINAL prefill:
        the request leaves "prefilling" with its first token. Requests
        that lost their slot while the chunk was in flight (cancel /
        deadline abort / preemption restart — epoch bumped) are
        skipped: their result belongs to a previous life."""
        now = time.perf_counter()
        for row, (si, req, epoch) in enumerate(group):
            if req.state != "prefilling" or req.epoch != epoch:
                continue
            tok = int(toks[row])
            req.state = "running"
            if self.tracer is not None:
                self._trace_running(req, now)
            self._mark_first_token(req, now)
            req.out_tokens.append(tok)
            req.planned = 1
            self.generated_tokens += 1
            self._last_tok[si] = tok
            self._fresh_slots.add(si)
            if self._is_finished(req):
                self._retire(si)

    def _is_finished(self, req: Request) -> bool:
        sp = req.sampling
        return (len(req.out_tokens) >= sp.max_new_tokens
                or (sp.eos_token_id is not None
                    and req.out_tokens[-1] == sp.eos_token_id))

    def _retire(self, si: int):
        req = self._slots[si]
        req.state = "done"
        req.t_done = time.perf_counter()
        # finished-request ITL samples fold into the bounded reservoir
        # here (aborted/failed lifetimes never reach _retire, so the
        # successful-traffic-only percentile contract is preserved)
        self._itl_res.extend(req.itls)
        if self.tracer is not None:
            if req.trace_id is not None:
                self._trace_life_end(req, "done", req.t_done)
                self.tracer.end_request(
                    req.trace_id, "done", replica=self.replica_id,
                    tokens=len(req.out_tokens))
            m = self.tracer.metrics
            if req.latency_s is not None:
                m.histogram("engine.latency_s").observe(req.latency_s)
            if req.ttft_s is not None:
                m.histogram("engine.ttft_s").observe(req.ttft_s)
        self._done[req.req_id] = req
        self._slots[si] = None
        self._lora_release(req)
        if self._inflight:
            # an in-flight chunk still reads/writes this request's pages
            # (it was dispatched assuming continuation): free them only
            # after the LAST dispatched chunk is fetched
            self._inflight[-1]["free_after"].append(req.req_id)
        else:
            self.dec.cache.free(req.req_id)

    def _zeros_seen(self, rows: int, vocab: int):
        """Cached device-resident all-False seen mask (per row count)."""
        cached = self._zeros_seen_cache.get(rows)
        if cached is None:
            cached = self._replicated(jnp.zeros((rows, vocab), bool))
            self._zeros_seen_cache[rows] = cached
        return cached

    def _ones_allowed(self, rows: int, vocab: int):
        """Cached device-resident all-True allowed mask: the identity
        operand every rich dispatch without structured-decoding
        requests ships (no [rows, vocab] host->device traffic)."""
        cached = self._ones_allowed_cache.get(rows)
        if cached is None:
            cached = self._replicated(jnp.ones((rows, vocab), bool))
            self._ones_allowed_cache[rows] = cached
        return cached

    def _allowed_operand(self, rows: int, entries):
        """The allowed-vocab operand for one rich dispatch: ``entries``
        is [(row, mask)] for the requests that restrict their vocab —
        empty reuses the cached all-True identity, and a repeated
        (rows, row->mask) layout reuses the memoized device operand
        (masks are per-request immutable, so a long-running masked
        stream uploads its [rows, vocab] operand once per layout, not
        once per dispatch)."""
        vocab = self.dec.cfg.vocab_size
        entries = [(r, m) for r, m in entries if m is not None]
        if not entries:
            return self._ones_allowed(rows, vocab)
        key = (rows, tuple(sorted((r, id(m)) for r, m in entries)))
        cached = self._allowed_memo.get(key)
        if cached is None:
            if len(self._allowed_memo) >= 256:
                # churn guard: an engine that never clear_finished()es
                # must not accumulate one [rows, vocab] device array
                # per dead layout forever
                self._allowed_memo.clear()
            allowed = np.ones((rows, vocab), bool)
            for r, m in entries:
                allowed[r] = m
            cached = self._replicated(jnp.asarray(allowed)) \
                if self.tp > 1 else jnp.asarray(allowed)
            self._allowed_memo[key] = cached
        return cached

    @staticmethod
    def _normalize_allowed(allowed_tokens, vocab: int) -> np.ndarray:
        """allowed_tokens (bool mask of length vocab, or a sequence of
        allowed token ids) -> [vocab] bool mask; rejects empty masks
        and out-of-range ids at add_request time."""
        arr = np.asarray(allowed_tokens)
        if arr.dtype == bool:
            if arr.shape != (vocab,):
                raise ValueError(
                    f"allowed_tokens bool mask must have shape "
                    f"({vocab},), got {arr.shape}")
            mask = arr.copy()
        else:
            if (arr.ndim == 1 and arr.size == vocab and vocab > 2
                    and np.isin(arr, (0, 1)).all()):
                # an INTEGER 0/1 vector of exactly vocab length is
                # almost certainly a mask built with the wrong dtype —
                # interpreting it as token IDS would silently constrain
                # decoding to tokens {0, 1}
                raise ValueError(
                    f"allowed_tokens is a length-{vocab} integer 0/1 "
                    f"vector — ambiguous between a mask and an id "
                    f"list; pass a bool mask (astype(bool)) or a list "
                    f"of allowed token ids")
            ids = arr.astype(np.int64).reshape(-1)
            if ids.size and (ids.min() < 0 or ids.max() >= vocab):
                raise ValueError(
                    f"allowed_tokens ids out of range [0, {vocab})")
            mask = np.zeros(vocab, bool)
            mask[ids] = True
        if not mask.any():
            raise ValueError("allowed_tokens permits no token — "
                             "nothing could ever be sampled")
        return mask

    # -- multi-tenant adapter bookkeeping (ISSUE 10) -------------------------
    def _lora_acquire(self, req: Request):
        """Fault/pin the request's adapter at admission. Raises
        KVCacheExhausted when its pages cannot be faulted in — the
        caller treats it exactly like a KV allocation refusal."""
        if req.sampling.adapter_id is None or req.lora_held:
            return
        self.lora.acquire(req.sampling.adapter_id)
        req.lora_held = True

    def _lora_release(self, req: Request):
        """Drop the request's pin whenever it loses its slot (retire,
        abort/fail, preemption/restart). At zero users the adapter's
        pages park in the pool LRU — still resident, evictable."""
        if req.lora_held:
            self.lora.release(req.sampling.adapter_id)
            req.lora_held = False

    def _lora_tables_operand(self, sched) -> np.ndarray:
        """[max_b + 1, n_pages] page table for this dispatch's lora
        gather: engine slot -> its request's resident adapter pages
        (scratch block — the all-zero null-adapter page — for
        base-model slots and the scratch row)."""
        width = self.lora.n_pages()
        tables = np.full((self.max_b + 1, width), self._scratch_block,
                         np.int32)
        for rid, (req, _epoch) in sched.items():
            aid = req.sampling.adapter_id
            if aid is not None and req.slot is not None:
                tables[req.slot] = self.lora.resident_blocks(aid)
        return tables

    def _debug_lora_check(self):
        """Cross-check registry use counts against the scheduler's
        slot truth, then the registry's own page invariants (the
        ISSUE-10 half of the per-step debug sweep)."""
        expected: Dict[object, int] = {}
        for r in self._slots:
            if r is not None and r.lora_held:
                aid = r.sampling.adapter_id
                expected[aid] = expected.get(aid, 0) + 1
        self.lora.debug_check(expected_use=expected)

    def _replicated(self, arr):
        """Commit a cached device constant consistently with the
        engine's mesh: replicated over the tp mesh under tensor
        parallelism (a default-device-committed constant would clash
        with the tp-mesh program), as-is otherwise. The spec is
        spelled DIMENSION-WISE (P(None, ..., None), not P()) to match
        the sharding the tp programs' own outputs carry: jit caches on
        the spelling, so a carried operand that alternates between a
        P() constant (first dispatch after idle) and a program output
        (every later dispatch) would trace+compile each (T, W) shape
        TWICE — a silent 2x compile tax CompileWatch caught on the
        sealed tp chaos leg (ISSUE 14)."""
        if self.tp == 1:
            return arr
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(
            arr, NamedSharding(self.dec.mesh, P(*(None,) * arr.ndim)))

    def _warmup_prompt(self, n: int) -> np.ndarray:
        """Throwaway warmup prompt with a per-call token fill: two
        warmup prompts must never share a block-aligned prefix, or the
        prefix cache would splice them together and the full-length
        (bucket, width) prefill programs warmup exists to compile would
        never run."""
        self._warmup_fill = getattr(self, "_warmup_fill", 0) + 1
        v = 1 + self._warmup_fill % max(1, self.dec.cfg.vocab_size - 1)
        return np.full(n, v, np.int32)

    def _rep_active(self) -> bool:
        return any(r is not None and r.state == "running"
                   and r.sampling.repetition_penalty != 1.0
                   for r in self._slots)

    def _pick_chunk(self, active) -> int:
        """Pick the ladder rung for this chunk.

        With a measured per-rung cost table (built by warmup): maximize
        delivered tokens per second — tokens(c) = sum over active slots
        of min(c, remaining budget); cost(c) was measured on THIS
        device/link. Overshooting a slot's budget (it idles on the
        scratch page for the tail) is chosen exactly when the per-chunk
        overhead (e.g. host↔device round trip) outweighs the wasted
        steps — a property of the deployment, not a constant.

        Without the table (warmup not run): zero-waste heuristic —
        largest rung every budget covers when idle; when requests are
        queued, largest rung the SOONEST-draining slot covers (so its
        slot frees promptly). Either way, queue pressure with EOS-able
        requests pins the smallest rung: such a slot may free any step.
        """
        if len(self.chunks) == 1:
            return self.chunks[0]
        if self._queue and any(
                self._slots[si].sampling.eos_token_id is not None
                for si in active):
            return self.chunks[0]
        lefts = [self._slots[si].sampling.max_new_tokens
                 - self._slots[si].planned for si in active]
        if self._chunk_cost:
            best, best_rate = self.chunks[0], -1.0
            for c in self.chunks:
                cost = self._chunk_cost.get(c)
                if cost is None:
                    continue
                tokens = sum(min(c, max(0, lf)) for lf in lefts)
                rate = tokens / cost
                if rate > best_rate + 1e-9:
                    best, best_rate = c, rate
            return best
        bound = min(lefts) if self._queue else max(lefts)
        best = self.chunks[0]
        for c in self.chunks[1:]:
            if c <= bound:
                best = c
        return best

    def _newest_decode_entry(self):
        for e in reversed(self._inflight):
            if e["kind"] == "decode":
                return e
        return None

    @_phased("engine.dispatch")
    def _dispatch_chunk(self) -> bool:
        """Dispatch ONE decode chunk for the current RUNNING slots
        without waiting for the previous chunk: first tokens of
        continuing slots are gathered from the in-flight chunk's DEVICE
        output (no host round trip); freshly admitted slots take their
        prefill token from the host. Slots still mid-prefill aim at the
        scratch page like inactive ones."""
        cache = self.dec.cache
        active = [si for si in range(self.max_b)
                  if self._slots[si] is not None
                  and self._slots[si].state == "running"]
        if not active:
            return False
        T = self._force_chunk or self._pick_chunk(active)
        mb, mp = self.max_b, self.dec.max_pages
        # host-precomputed page schedule: slots past their token budget
        # (or inactive) aim at the scratch page for the rest of the chunk
        tables = np.full((T, mb, mp), self._scratch_block, np.int32)
        ctx = np.zeros((T, mb), np.int32)
        slots = np.full((T, mb), self._scratch_slot, np.int32)
        temps = np.zeros(mb, np.float32)
        top_ks = np.zeros(mb, np.int32)
        top_ps = np.ones(mb, np.float32)
        reps = np.ones(mb, np.float32)
        vocab = self.dec.cfg.vocab_size
        steps_of: Dict[int, int] = {}
        reqs_of: Dict[int, Request] = {}
        epochs_of: Dict[int, int] = {}
        def neutralize(vsi: int):
            """Blank a slot's rows in THIS chunk's schedule. A victim
            preempted mid-build frees blocks a LATER slot of the same
            chunk may take — but its already-scheduled rows would then
            write K/V into the same flat slots within ONE program,
            silently corrupting the surviving request (device program
            order only protects cross-program reuse). Re-aiming the
            victim's rows at the scratch page removes the overlap.
            The victim's sampling contribution is dropped too: a
            processed row would otherwise keep the whole chunk on the
            rich program (unwarmed XLA variant + [mb, vocab] seen
            matrix) even when every surviving row is greedy."""
            slots[:, vsi] = self._scratch_slot
            ctx[:, vsi] = 0
            tables[:, vsi, :] = self._scratch_block
            steps_of.pop(vsi, None)
            reqs_of.pop(vsi, None)
            epochs_of.pop(vsi, None)
            temps[vsi] = 0.0
            top_ks[vsi] = 0
            top_ps[vsi] = 1.0
            reps[vsi] = 1.0

        for si in active:
            req = self._slots[si]
            if req is None or req.state != "running":
                # preempted by an earlier slot's KV pressure while this
                # chunk was being scheduled
                continue
            sp = req.sampling
            # budget at DISPATCH time: tokens planned (dispatched), not
            # tokens fetched — EOS cuts are discovered at collection
            steps = max(0, min(T, sp.max_new_tokens - req.planned))
            try:
                for t in range(steps):
                    ctx[t, si] = cache.context_len(req.req_id)
                    while True:
                        try:
                            slots[t, si] = cache.extend(req.req_id)
                            break
                        except KVCacheExhausted:
                            victim = self._pick_victim()
                            if victim is None or victim is req:
                                raise
                            vsi = victim.slot
                            self._preempt(victim)
                            if vsi is not None:
                                neutralize(vsi)
            except KVCacheExhausted:
                # req itself is the policy victim (newest / lowest
                # priority): preempt it and blank its partial rows —
                # its freed pages may be re-taken by a later slot of
                # this very chunk. A recompute-incapable decoder has
                # no resume programs (_pick_victim always returns None
                # for it), so preempting would re-admit into a mid
                # path that doesn't exist — fail the request instead.
                if self._can_recompute:
                    self._preempt(req)
                else:
                    self._fail_request(
                        req, "KV pool exhausted and decoder does not "
                             "support preemption-with-recompute")
                neutralize(si)
                continue
            req.planned += steps
            steps_of[si] = steps
            reqs_of[si] = req
            epochs_of[si] = req.epoch
            temps[si] = sp.temperature
            top_ks[si] = self.top_k if sp.top_k is None else sp.top_k
            top_ps[si] = sp.top_p
            reps[si] = sp.repetition_penalty
            # one table per slot per chunk: after the extends above the
            # block list is final for the whole chunk, and entries past
            # a step's context length are masked by ctx anyway
            tables[:, si, :] = cache.block_table(req.req_id, mp)[None]
        # computed over SURVIVORS only — neutralize() may have dropped
        # an already-accumulated victim row
        rich = any(r.sampling.needs_rich_sampling
                   for r in reqs_of.values())
        if all(s == 0 for s in steps_of.values()):
            # every active slot is budget-drained and just awaiting
            # collection — nothing to run
            return False

        # first tokens: device gather from the newest in-flight DECODE
        # chunk for continuing slots, host values for fresh/0-step
        # slots (prefill entries between them don't carry decode toks)
        try:
            prev = self._newest_decode_entry()
            if prev is not None:
                last_idx = np.zeros(mb, np.int32)
                override = np.asarray(self._last_tok, np.int32).copy()
                use_host = np.ones(mb, bool)
                for si, req in reqs_of.items():
                    psteps = prev["steps"].get(si, 0)
                    if (psteps > 0 and si not in self._fresh_slots
                            and prev["reqs"].get(si) is req
                            and prev["epochs"].get(si) == req.epoch):
                        use_host[si] = False
                        last_idx[si] = psteps - 1
                first_ids = self._device_call(
                    "dispatch:merge", self._merge_first_j,
                    prev["toks"], jnp.asarray(last_idx),
                    jnp.asarray(override), jnp.asarray(use_host))
            else:
                first_ids = jnp.asarray(self._last_tok)
            self._fresh_slots.clear()

            keys = jax.random.split(self._next_key(), T)
            if rich:
                if any(r.sampling.repetition_penalty != 1.0
                       for r in reqs_of.values()):
                    seen = np.zeros((mb, vocab), bool)
                    for si, req in reqs_of.items():
                        if req.sampling.repetition_penalty != 1.0:
                            seen[si, req.prompt] = True
                            if req.out_tokens:
                                seen[si,
                                     np.asarray(req.out_tokens)] = True
                    seen_dev = jnp.asarray(seen)
                else:
                    # top_k/top_p-only chunk: the mask is multiplied by
                    # (rep != 1) == False in-program — reuse a cached
                    # device-resident zeros mask instead of shipping
                    # [mb, vocab] bools to the device every chunk
                    seen_dev = self._zeros_seen(mb, vocab)
                allowed_dev = self._allowed_operand(
                    mb, [(si, r.allowed_mask)
                         for si, r in reqs_of.items()])
                self.masked_decode_columns += sum(
                    1 for si, r in reqs_of.items()
                    if r.allowed_mask is not None
                    and steps_of.get(si, 0) > 0)
                toks, cache.k, cache.v = self._device_call(
                    "dispatch:decode", self._decode_rich_j,
                    self.dec.weights, cache.k, cache.v, first_ids,
                    jnp.asarray(tables), jnp.asarray(ctx),
                    jnp.asarray(slots), jnp.asarray(temps), keys,
                    jnp.asarray(top_ks), jnp.asarray(top_ps),
                    jnp.asarray(reps), seen_dev, allowed_dev)
            else:
                toks, cache.k, cache.v = self._device_call(
                    "dispatch:decode", self._decode_j,
                    self.dec.weights, cache.k, cache.v, first_ids,
                    jnp.asarray(tables), jnp.asarray(ctx),
                    jnp.asarray(slots), jnp.asarray(temps), keys)
        except _DispatchFailed as e:
            # transient device error that survived the retry budget:
            # the chunk's requests fail with a structured error — the
            # ENGINE keeps serving (0-step slots awaiting collection
            # and still-prefilling requests are untouched)
            for si, steps in steps_of.items():
                req = reqs_of[si]
                if steps > 0 and self._slots[si] is req \
                        and req.state == "running":
                    self._fail_request(
                        req, f"decode dispatch failed after retries: "
                             f"{e}")
            return False
        if self.tracer is not None:
            self.tracer.event(
                "dispatch", pid=self.replica_id, kind="decode",
                T=int(T), width=self.max_b,
                rows=sum(1 for s in steps_of.values() if s > 0),
                tokens=int(sum(steps_of.values())))
        self._ph.set(dispatch=self._dispatch_seq, T=int(T),
                     decode_cols=len(steps_of))
        self._inflight.append({"kind": "decode", "toks": toks,
                               "seq": self._dispatch_seq,
                               "steps": steps_of, "reqs": reqs_of,
                               "epochs": epochs_of,
                               "T": T, "free_after": []})
        return True

    # -- ragged unified scheduler (ISSUE 5) ----------------------------------
    # row-count buckets for the ragged [T, W] schedule: W pads up to the
    # next rung (the ONLY padding left on this path — stats() counts it
    # as padded_token_waste), so compile variants stay ~log-bounded
    RAGGED_WIDTHS = (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128)
    # prefill rows per pure-prefill (idle) ragged program: no decode
    # stream is waiting, so bursts drain in few wide programs instead
    # of being serialized across steps by the interleaving budget
    _RAGGED_IDLE_CAP = 256

    def _ragged_width(self, w: int) -> int:
        for b in self.RAGGED_WIDTHS:
            if w <= b:
                return b
        return -(-w // 64) * 64

    def _newest_ragged_entry(self):
        for e in reversed(self._inflight):
            if e["kind"] == "ragged":
                return e
        return None

    def _zeros_toks(self, t: int, w: int):
        """Cached device-resident zero [T, W] token block: the
        prev-toks operand of the FIRST ragged dispatch after a pipeline
        flush (every column takes its host override)."""
        cached = self._zeros_toks_cache.get((t, w))
        if cached is None:
            cached = self._replicated(jnp.zeros((t, w), jnp.int32))
            self._zeros_toks_cache[(t, w)] = cached
        return cached

    def _ragged_plan(self):
        """(T, dcols, takes, fused): this step's decode columns and
        prefill token takes, computed WITHOUT touching the allocator —
        the shape pre-pass that fixes the (T, W) program variant before
        any page is claimed (so a variant mismatch with the in-flight
        chunk can flush the pipeline BEFORE the schedule is built).
        ``fused`` marks a multi-step window (ISSUE 16): in the
        pure-decode regime — running slots, NO prefilling slot — the
        plan scales the chunk rung to k*T ministeps, fusing k serving
        steps into one program; any prefilling slot (mid-prefill,
        splice-pending, fresh admission) drops back to single-step
        chunks so chunked-prefill ITL bounds and splice watermarks
        keep their per-step granularity."""
        running = [si for si in range(self.max_b)
                   if self._slots[si] is not None
                   and self._slots[si].state == "running"]
        T = self._force_chunk or (self._pick_chunk(running) if running
                                  else 1)
        fused = (self.multi_step > 1 and bool(running)
                 and not any(r is not None and r.state == "prefilling"
                             for r in self._slots))
        if fused:
            T = T * self.multi_step
        dcols = []
        for si in running:
            req = self._slots[si]
            steps = max(0, min(T, req.sampling.max_new_tokens
                               - req.planned))
            if steps > 0:
                dcols.append((si, req, steps))
        takes = []
        # while decodes run, the budget bounds how much prefill slots
        # between consecutive decode ministep groups (the running
        # streams' worst-case added ITL — dense-path semantics); an
        # idle engine widens to drain bursts in few programs, and
        # _dispatch_ragged keeps issuing pure-prefill chunks until the
        # backlog is gone
        budget = self._ragged_cap if dcols \
            else max(self._ragged_cap, self._ragged_idle_cap)
        pending = sorted((r for r in self._slots
                          if r is not None and r.state == "prefilling"
                          and r.prefill_sent < r.suffix_len),
                         key=lambda r: r.req_id)
        for r in pending:
            if budget <= 0:
                break
            if not self._deps_ready(r):
                # splice-pending reader: its writer's covering chunk has
                # not been DISPATCHED yet (same watermark rule as the
                # dense path — a reader never rides the same or an
                # earlier program than its writer's covering rows)
                continue
            take = min(budget, r.suffix_len - r.prefill_sent)
            takes.append((r, take))
            budget -= take
        return T, dcols, takes, fused

    def _dispatch_ragged(self) -> bool:
        """Dispatch this step's ragged work: the speculative verify
        chunk when drafting applies (ISSUE 9 — greedy decode columns
        with draft hits), else ONE unified chunk in the steady mixed
        regime; a pure-prefill backlog (no running decodes — cold
        start, burst admission) keeps issuing bounded prefill-only
        chunks until nothing is ready, mirroring the dense idle path's
        unbudgeted _dispatch_prefill (each program is dispatched
        before the next is built, so a splice reader's same-step
        chunks still follow its writer's in device order)."""
        if self._dispatch_spec_chunk():
            return True
        if not self._dispatch_ragged_chunk():
            return False
        while (not any(r is not None and r.state == "running"
                       for r in self._slots)
               and self._dispatch_ragged_chunk()):
            pass
        return True

    # -- speculative decoding (ISSUE 9) --------------------------------------
    def _spec_probe(self) -> bool:
        """Would ANY running greedy column draft right now, judged on
        the possibly-stale (in-flight-chunk-lagged) history? Pure host
        work — used to decide whether a pipeline flush is worth
        paying; windows are never built from this, only from flushed
        truth in _dispatch_spec_chunk."""
        for r in self._slots:
            if (r is None or r.state != "running"
                    or r.sampling.temperature > 0.0
                    or r.sampling.needs_rich_sampling):
                continue
            left = r.sampling.max_new_tokens - r.planned
            if left <= 1:
                continue
            hist = np.concatenate(
                [r.prompt, np.asarray(r.out_tokens, np.int32)])
            if np.asarray(self._drafter.propose(
                    hist, min(self.spec.draft_len, left - 1))).size:
                return True
        return False

    def _dispatch_spec_chunk(self) -> bool:
        """Dispatch ONE speculative verify+decode chunk: every greedy
        running column rides as 1 + k ragged rows (its carried token
        plus the drafter's k proposals at consecutive positions), the
        teacher verifies all positions in a single forward, and
        acceptance/neutralization happen in-program (_spec_accept).
        Prefill-chunk rows ride along under what is left of the
        per-step row budget after the draft fan-out. Returns False —
        the caller falls back to the plain ragged chunk — when spec is
        off, any slotted request needs rich sampling (its per-column
        seen-mask semantics don't compose with multi-row columns), no
        column is running, or the drafter proposed nothing this step
        (a 1-ministep chunk with no drafts is strictly worse than the
        T-ministep ragged program).

        The verify chunk is SYNCHRONOUS by construction (step()
        collects it before returning): the accepted count decides the
        next step's positions, slots and drafts, so there is nothing
        correct to pipeline behind it. The flush below also makes the
        drafter's history exact — an in-flight chunk's tokens are
        device-side and drafting against stale history would verify
        the wrong positions."""
        if self.spec is None:
            return False
        if any(r is not None and r.sampling.needs_rich_sampling
               for r in self._slots):
            return False
        if not any(r is not None and r.state == "running"
                   for r in self._slots):
            return False
        # cheap probe on the CURRENT (at most one-chunk-stale) history
        # BEFORE paying the pipeline flush: on a low-hit workload the
        # drafter misses every step, and flushing first would disable
        # the ragged path's overlap permanently. A probe hit flushes
        # and re-proposes against exact history (a window is only ever
        # BUILT from flushed truth); a probe miss that exact history
        # would have hit merely delays spec by one step.
        if self._inflight and not self._spec_probe():
            return False
        while self._inflight:
            self._collect_oldest()
        return self._build_spec_chunk()

    @_phased("engine.dispatch")
    def _build_spec_chunk(self) -> bool:
        """Draft, build and dispatch the verify chunk (the body of
        ``_dispatch_spec_chunk``, on flushed history)."""
        cache = self.dec.cache
        mp = self.dec.max_pages
        dcols: List[Tuple[int, Request, np.ndarray]] = []
        total_drafts = 0
        for si in range(self.max_b):
            req = self._slots[si]
            if req is None or req.state != "running":
                continue
            left = req.sampling.max_new_tokens - req.planned
            if left <= 0:
                continue
            drafts = np.zeros(0, np.int32)
            if req.sampling.temperature <= 0.0 and left > 1:
                # drafts clamp to the window AND the remaining budget
                # (re-clipped after propose: a Drafter that ignores
                # its k contract must not inflate the verify window or
                # starve the prefill row budget): a draft past either
                # bound could never be delivered — pure row waste
                k = min(self.spec.draft_len, left - 1)
                hist = np.concatenate(
                    [req.prompt, np.asarray(req.out_tokens, np.int32)])
                drafts = np.asarray(
                    self._drafter.propose(hist, k),
                    np.int32).reshape(-1)[:k]
            dcols.append((si, req, drafts))
            total_drafts += len(drafts)
        if total_drafts == 0:
            return False
        # draft rows COMPETE with prefill chunks under the per-step
        # row budget: both are extra rows of the same program, and the
        # budget is the bound on the running streams' added ITL
        budget = max(0, self._ragged_cap - total_drafts)
        takes: List[Tuple[Request, int]] = []
        pending = sorted((r for r in self._slots
                          if r is not None and r.state == "prefilling"
                          and r.prefill_sent < r.suffix_len),
                         key=lambda r: r.req_id)
        for r in pending:
            if budget <= 0:
                break
            if not self._deps_ready(r):
                continue
            take = min(budget, r.suffix_len - r.prefill_sent)
            takes.append((r, take))
            budget -= take

        rows = sum(1 + len(d) for _, _, d in dcols) \
            + sum(t for _, t in takes)
        W = self._ragged_width(rows)
        scratch_row = self.max_b
        ids = np.zeros(W, np.int32)
        pos = np.zeros(W, np.int32)
        slots = np.full(W, self._scratch_slot, np.int32)
        rseq = np.full(W, scratch_row, np.int32)
        rctx = np.zeros(W, np.int32)
        use_ov = np.zeros(W, bool)
        override = np.zeros(W, np.int32)
        temps = np.zeros(W, np.float32)
        seg_start = np.arange(W, dtype=np.int32)
        is_draft = np.zeros(W, bool)
        rows_of: Dict[int, List[int]] = {}       # req_id -> rows
        sched: Dict[int, Tuple[Request, int]] = {}
        spec_of: Dict[int, dict] = {}            # slot -> verify window
        finals: List[Tuple[Request, int, int]] = []
        take_of: Dict[int, int] = {}
        col = 0
        for si, req, drafts in dcols:
            if self._slots[si] is not req or req.state != "running":
                continue   # evicted by an earlier column's KV pressure
            base, span = col, 1 + len(drafts)
            col += span    # the run stays reserved even if preempted
            cells = rows_of.setdefault(req.req_id, [])
            # pre-register (like the ragged chunk): when req becomes
            # its own victim mid-extend the staleness sweep must see
            # it to blank its partial rows
            sched[req.req_id] = (req, req.epoch)
            ctx0 = cache.context_len(req.req_id)
            # table length BEFORE the window's extends: rollback may
            # drop only blocks the window itself appended — a
            # worst-case admission reservation must survive intact
            tbl0 = len(cache.seq_blocks(req.req_id))
            done = 0
            try:
                for j in range(span):
                    c = base + j
                    p = ctx0 + j
                    slot = self._extend_with_preempt(req)
                    slots[c] = slot
                    pos[c] = p
                    rctx[c] = p + 1   # sees context + earlier drafts
                    rseq[c] = si
                    cells.append(c)
                    if j == 0:
                        # the carried token always comes from the host
                        # here: the pipeline was flushed above, so the
                        # last emitted token is host-known by def.
                        use_ov[c] = True
                        override[c] = self._last_tok[si]
                    else:
                        ids[c] = int(drafts[j - 1])
                        is_draft[c] = True
                        seg_start[c] = base
                    done += 1
            except KVCacheExhausted:
                # no preemption victim left for the window's tail.
                # With the BASE row scheduled, degrade gracefully:
                # truncate the window to the rows the pool granted (a
                # k=0 window is a plain decode row) — self-preempting
                # here would replay the identical oversized window on
                # resume and livelock under exactly the pressure that
                # made the pool refuse. Only a base row that cannot
                # extend at all preempts (or fails, on a
                # recompute-incapable decoder), like the ragged path.
                if done == 0:
                    if self._can_recompute:
                        self._preempt(req)
                    else:
                        self._fail_request(
                            req, "KV pool exhausted and decoder does "
                                 "not support "
                                 "preemption-with-recompute")
                    continue
            # per-row temperature over the whole window: a draftable
            # column is greedy (temp <= 0) by construction, but a
            # plain-temperature stochastic column rides as a 1-row
            # window and must keep SAMPLING (its stream is not pinned
            # across spec on/off — the key consumption differs — but
            # it must stay a sample, not silently turn greedy)
            temps[base:base + done] = req.sampling.temperature
            # collection needs only the window geometry: acceptance is
            # read off the program's in-program mask (the draft values
            # already live in the dispatched ids schedule)
            spec_of[si] = {"req": req, "epoch": req.epoch,
                           "base": base, "k": done - 1,
                           "ctx0": ctx0, "tbl0": tbl0}
        # prefill rows after the verify windows. Every row is its own
        # column at T=1, so the ragged chunk's one-sampling-final-per-
        # column constraint is satisfied for free; rich finals cannot
        # appear (spec pauses while any slotted request is rich).
        pi = col
        for req, take in takes:
            if req.state != "prefilling" or req.slot is None:
                continue   # evicted by decode-side pressure mid-build
            si = req.slot
            toks_src = req.prefill_tokens
            base_off = req.n_cached + req.prefill_sent
            cells = rows_of.setdefault(req.req_id, [])
            sched[req.req_id] = (req, req.epoch)
            scheduled = 0
            try:
                for j in range(take):
                    if pi >= W:
                        break
                    off = base_off + j
                    c = pi
                    slot = self._extend_with_preempt(req)
                    ids[c] = int(toks_src[off])
                    pos[c] = off
                    rctx[c] = off + 1
                    slots[c] = slot
                    rseq[c] = si
                    cells.append(c)
                    scheduled += 1
                    pi += 1
                    if not req.resume and off + 1 == len(toks_src):
                        temps[c] = req.sampling.temperature
                        finals.append((req, req.epoch, c))
            except KVCacheExhausted as e:
                self._fail_request(
                    req, f"KV pool exhausted mid-prefill with no "
                         f"preemption victim: {e}")
                continue
            if scheduled:
                take_of[req.req_id] = scheduled

        # staleness sweep (the ragged chunk's, at one ministep): blank
        # every row of every request that lost its life mid-build
        def blank(cell_list):
            for c in cell_list:
                ids[c] = 0
                pos[c] = 0
                slots[c] = self._scratch_slot
                rseq[c] = scratch_row
                rctx[c] = 0
                temps[c] = 0.0
                use_ov[c] = False
                override[c] = 0
                is_draft[c] = False
                seg_start[c] = c

        for rid in list(sched):
            req, epoch = sched[rid]
            if (req.epoch == epoch and req.slot is not None
                    and req.state in ("running", "prefilling")):
                continue
            blank(rows_of.get(rid, []))
            for vsi in [s for s, ent in spec_of.items()
                        if ent["req"] is req]:
                del spec_of[vsi]
            take_of.pop(rid, None)
            finals[:] = [f for f in finals if f[0] is not req]
            del sched[rid]
        if not sched:
            return False

        tables = np.full((self.max_b + 1, mp), self._scratch_block,
                         np.int32)
        for rid, (req, epoch) in sched.items():
            tables[req.slot] = cache.block_table(req.req_id, mp)
        self._fresh_slots.clear()

        key = self._replicated(self._next_key())
        aj = self._aj
        use_lora = self.lora is not None and any(
            req.sampling.adapter_id is not None
            for req, _e in sched.values())
        pre = ()
        prog = self._spec_j
        if use_lora:
            pre = (cache.lora_pool, self._shard_ids,
                   aj(self._lora_tables_operand(sched)))
            prog = self._spec_lora_j
            self.lora_dispatches += 1
            self.lora_rows += sum(
                len(rows_of.get(rid, []))
                for rid, (req, _e) in sched.items()
                if req.sampling.adapter_id is not None)
        args = (self.dec.weights, cache.k, cache.v) + pre + (
            aj(override),
            aj(use_ov), aj(ids), aj(pos), aj(slots), aj(rseq),
            aj(rctx), aj(tables), aj(temps), key, aj(seg_start),
            aj(is_draft))
        try:
            toks, acc, cache.k, cache.v = self._device_call(
                "dispatch:spec", prog, *args)
        except _DispatchFailed as e:
            # one program: every surviving request riding it fails
            # together (the ragged chunk's failure contract)
            for rid, (req, epoch) in sched.items():
                if req.epoch == epoch and req.state in ("running",
                                                        "prefilling"):
                    self._fail_request(
                        req, f"spec dispatch failed after retries: "
                             f"{e}")
            return False

        for rid, (req, epoch) in sched.items():
            take = take_of.get(rid, 0)
            if take and req.state == "prefilling":
                req.prefill_sent += take
                if req.prefill_sent >= req.suffix_len:
                    if req.resume:
                        self._resume_complete(req)
                    else:
                        self._clear_pending_writes(req)
        if self.tracer is not None:
            self.tracer.event(
                "dispatch", pid=self.replica_id, kind="spec",
                W=int(W), drafts=int(total_drafts),
                decode_cols=len(spec_of),
                prefill_rows=int(sum(take_of.values())))
        self._ph.set(dispatch=self._dispatch_seq, W=int(W),
                     decode_cols=len(spec_of),
                     prefill_tokens=int(sum(take_of.values())))
        self._inflight.append({
            "kind": "spec", "seq": self._dispatch_seq,
            "toks": toks, "acc": acc, "W": W,
            "spec": spec_of, "finals": list(finals),
            "real_rows": sum(take_of.values()),
            "free_after": []})
        return True

    def _dispatch_ragged_chunk(self) -> bool:
        """Dispatch ONE unified ragged chunk — the whole step's device
        work as a single program: T sequential ministeps over a ragged
        [W]-row token batch whose columns are the running slots' decode
        tokens (sampled in-program, carried ministep-to-ministep, first
        tokens merged in-program from the previous chunk's device
        output) and this step's prefill-chunk tokens (no-sample rows at
        their global offsets, spread across the T ministeps; a prompt's
        final token row samples the request's first token). W is sized
        by the actual rows (bucketed), so inactive batch slots cost
        nothing. Preemption mid-build NEUTRALIZES the victim's ROW
        RANGE (every cell it was scheduled into is re-aimed at the
        scratch page — its freed blocks may be re-taken by later rows
        of this very chunk, and intra-program slot overlap would
        corrupt the survivor's KV), the ragged analogue of the dense
        path's neutralize-by-column. Returns True when dispatched."""
        with self._phase("engine.plan"):
            plan = self._ragged_plan()
        if not plan[1] and not plan[2]:
            return False
        return self._build_ragged_chunk(plan)

    @_phased("engine.dispatch")
    def _build_ragged_chunk(self, plan) -> bool:
        """Build the schedule ``plan`` describes and dispatch it (the
        body of ``_dispatch_ragged_chunk``)."""
        cache = self.dec.cache
        mp = self.dec.max_pages
        T, dcols, takes, fused = plan
        ptotal = sum(t for _, t in takes)
        W = self._ragged_width(len(dcols)
                               + (-(-ptotal // T) if ptotal else 0))
        prev = self._newest_ragged_entry()
        if prev is not None and prev["T"] == T and W < prev["W"]:
            # sticky width: a shrink (slot retired, prefill drained)
            # pads up to the in-flight chunk's width instead of
            # flushing the pipeline — only growth forces a flush
            W = prev["W"]
        if prev is not None and (prev["T"] != T or prev["W"] != W):
            # program-variant change (slots came or went, prefill phase
            # shifted): flush the pipeline so first tokens come from
            # the host — the in-program merge consumes the previous
            # chunk's [T, W] output and shapes must line up
            while self._inflight:
                self._collect_oldest()
            # collection may retire slots / deliver first tokens:
            # re-plan against the post-flush scheduler state
            T, dcols, takes, fused = self._ragged_plan()
            if not dcols and not takes:
                return False
            ptotal = sum(t for _, t in takes)
            W = self._ragged_width(len(dcols)
                                   + (-(-ptotal // T) if ptotal else 0))
            prev = None

        scratch_row = self.max_b
        vocab = self.dec.cfg.vocab_size
        ids = np.zeros((T, W), np.int32)
        pos = np.zeros((T, W), np.int32)
        slots = np.full((T, W), self._scratch_slot, np.int32)
        rseq = np.full((T, W), scratch_row, np.int32)
        rctx = np.zeros((T, W), np.int32)
        ucar = np.zeros((T, W), bool)
        temps = np.zeros((T, W), np.float32)
        top_ks = np.zeros((T, W), np.int32)
        top_ps = np.ones((T, W), np.float32)
        reps = np.ones((T, W), np.float32)
        upd = np.zeros(W, bool)
        rows_of: Dict[int, List[Tuple[int, int]]] = {}  # req_id -> cells
        sched: Dict[int, Tuple[Request, int]] = {}  # req_id -> (req, epoch)
        col_of: Dict[int, int] = {}                 # decode si -> column
        steps_of: Dict[int, int] = {}
        reqs_of: Dict[int, Request] = {}
        epochs_of: Dict[int, int] = {}
        take_of: Dict[int, int] = {}     # req_id -> prefill rows scheduled
        finals: List[Tuple[Request, int, int, int]] = []

        # decode columns --------------------------------------------------
        col = 0
        for si, req, steps in dcols:
            if self._slots[si] is not req or req.state != "running":
                # preempted by an earlier column's KV pressure while
                # this chunk was being built
                continue
            sp = req.sampling
            cells = rows_of.setdefault(req.req_id, [])
            # register BEFORE the allocator loop (like the prefill loop
            # below): when req becomes its own preemption victim
            # mid-extend, the staleness sweep only blanks rows of
            # requests it can see in `sched` — an unregistered victim's
            # partial rows would keep aiming reshape_and_cache at its
            # freed pages, which a later row of this very chunk may
            # re-take
            sched[req.req_id] = (req, req.epoch)
            try:
                for t in range(steps):
                    ctx = cache.context_len(req.req_id)
                    slot = self._extend_with_preempt(req)
                    pos[t, col] = ctx
                    rctx[t, col] = ctx + 1
                    slots[t, col] = slot
                    rseq[t, col] = si
                    cells.append((t, col))
            except KVCacheExhausted:
                # req itself is the policy victim (already in `sched`,
                # so the staleness sweep below blanks its partial rows
                # — _preempt bumps the epoch, _fail_request leaves the
                # running state)
                if self._can_recompute:
                    self._preempt(req)
                else:
                    self._fail_request(
                        req, "KV pool exhausted and decoder does not "
                             "support preemption-with-recompute")
                col += 1
                continue
            req.planned += steps
            ucar[:, col] = True
            temps[:, col] = sp.temperature
            top_ks[:, col] = self.top_k if sp.top_k is None else sp.top_k
            top_ps[:, col] = sp.top_p
            reps[:, col] = sp.repetition_penalty
            upd[col] = True
            col_of[si] = col
            steps_of[si] = steps
            reqs_of[si] = req
            epochs_of[si] = req.epoch
            col += 1

        # prefill cells: ministep-major past the decode columns, so a
        # request's tokens are sequential across (t, col) order — a row
        # always lands at the same or a later ministep than every
        # same-sequence row before it (pool writes precede attention
        # within a ministep, so intra-chunk causality holds by row_ctx)
        pcells = [(t, c) for t in range(T) for c in range(col, W)]
        pi = 0
        for req, take in takes:
            if req.state != "prefilling" or req.slot is None:
                continue   # evicted by decode-side pressure mid-build
            si = req.slot
            toks_src = req.prefill_tokens
            base_off = req.n_cached + req.prefill_sent
            cells = rows_of.setdefault(req.req_id, [])
            sched[req.req_id] = (req, req.epoch)
            scheduled = 0
            try:
                for j in range(take):
                    if pi >= len(pcells):
                        break
                    off = base_off + j
                    t, c = pcells[pi]
                    is_final = (not req.resume
                                and off + 1 == len(toks_src))
                    if is_final:
                        # at most one sampling final per COLUMN: its
                        # rich seen mask is seeded per column. Keep
                        # advancing — the next cell's column can hold
                        # an earlier final too (finals of short takes
                        # land on adjacent columns)
                        while any(fc == c for _, _, _, fc in finals):
                            pi += 1
                            if pi >= len(pcells):
                                break
                            t, c = pcells[pi]
                        if pi >= len(pcells):
                            break
                    slot = self._extend_with_preempt(req)
                    ids[t, c] = int(toks_src[off])
                    pos[t, c] = off
                    rctx[t, c] = off + 1
                    slots[t, c] = slot
                    rseq[t, c] = si
                    cells.append((t, c))
                    scheduled += 1
                    pi += 1
                    if is_final:
                        sp = req.sampling
                        temps[t, c] = sp.temperature
                        top_ks[t, c] = (self.top_k if sp.top_k is None
                                        else sp.top_k)
                        top_ps[t, c] = sp.top_p
                        reps[t, c] = sp.repetition_penalty
                        finals.append((req, req.epoch, t, c))
            except KVCacheExhausted as e:
                self._fail_request(
                    req, f"KV pool exhausted mid-prefill with no "
                         f"preemption victim: {e}")
                continue
            if scheduled:
                take_of[req.req_id] = scheduled

        # staleness sweep: neutralize the ROW RANGE of every request
        # that lost its life while the chunk was being built (direct
        # preemption victims AND cascaded reader restarts) — runs
        # BEFORE dispatch, so a blanked row never writes into pages a
        # survivor re-took
        def blank(cell_list):
            for t, c in cell_list:
                ids[t, c] = 0
                pos[t, c] = 0
                slots[t, c] = self._scratch_slot
                rseq[t, c] = scratch_row
                rctx[t, c] = 0
                temps[t, c] = 0.0
                top_ks[t, c] = 0
                top_ps[t, c] = 1.0
                reps[t, c] = 1.0

        for rid in list(sched):
            req, epoch = sched[rid]
            if (req.epoch == epoch and req.slot is not None
                    and req.state in ("running", "prefilling")):
                continue
            blank(rows_of.get(rid, []))
            for si in [s for s, r in reqs_of.items() if r is req]:
                c = col_of.pop(si, None)
                if c is not None:
                    upd[c] = False
                steps_of.pop(si, None)
                reqs_of.pop(si, None)
                epochs_of.pop(si, None)
            take_of.pop(rid, None)
            finals[:] = [f for f in finals if f[0] is not req]
            del sched[rid]
        if not sched:
            # everything scheduled was evicted mid-build
            return False

        # one table row per slot (plus the scratch row at max_b): after
        # the extends above every survivor's block list is final for
        # the whole chunk; entries past a row's ctx are masked anyway
        tables = np.full((self.max_b + 1, mp), self._scratch_block,
                         np.int32)
        for rid, (req, epoch) in sched.items():
            tables[req.slot] = cache.block_table(req.req_id, mp)

        # first decode tokens: previous ragged chunk's device output
        # for continuing columns (merged IN-program), host values for
        # fresh slots — prev["cols"] maps slots to the PREVIOUS chunk's
        # column layout, which need not match this one's
        last_t = np.zeros(W, np.int32)
        prev_col = np.zeros(W, np.int32)
        use_host = np.ones(W, bool)
        override = np.zeros(W, np.int32)
        for si, c in col_of.items():
            req = reqs_of[si]
            override[c] = self._last_tok[si]
            if prev is not None:
                pc = prev["cols"].get(si)
                psteps = prev["steps"].get(si, 0)
                if (pc is not None and psteps > 0
                        and si not in self._fresh_slots
                        and prev["reqs"].get(si) is req
                        and prev["epochs"].get(si) == req.epoch):
                    use_host[c] = False
                    prev_col[c] = pc
                    last_t[c] = psteps - 1
        self._fresh_slots.clear()

        rich = any(r.sampling.needs_rich_sampling
                   for r in reqs_of.values()) \
            or any(f[0].sampling.needs_rich_sampling for f in finals)
        # multi-tenant routing (ISSUE 10): any surviving scheduled
        # request with an adapter routes the whole chunk through the
        # lora program family (base rows read the null page — zero
        # delta); an all-base chunk keeps the UNCHANGED base program,
        # so adapter_id=None traffic is bit-identical to a lora-less
        # engine
        use_lora = self.lora is not None and any(
            req.sampling.adapter_id is not None
            for req, _e in sched.values())
        prev_toks = prev["toks"] if prev is not None \
            else self._zeros_toks(T, W)
        eos = None
        if fused:
            # on-device EOS bookkeeping operand: each surviving decode
            # column's EOS id (-1 = no EOS configured — the column
            # never freezes; the host still cuts at max_new via the
            # steps clamp). Built AFTER the staleness sweep so a
            # blanked column keeps -1 like any other scratch column.
            eos = np.full(W, -1, np.int32)
            for si, c in col_of.items():
                e = reqs_of[si].sampling.eos_token_id
                if e is not None:
                    eos[c] = e
            self.ms_windows += 1
        # under tp the split keys (committed to the default device)
        # re-place replicated on the tp mesh — an async device_put,
        # not a host sync; the key VALUES are identical to the tp=1
        # stream, only the placement changes
        keys = self._replicated(jax.random.split(self._next_key(), T))
        aj = self._aj
        pre = ()
        if use_lora:
            pre = (cache.lora_pool, self._shard_ids,
                   aj(self._lora_tables_operand(sched)))
            self.lora_dispatches += 1
            self.lora_rows += sum(
                len(rows_of.get(rid, []))
                for rid, (req, _e) in sched.items()
                if req.sampling.adapter_id is not None)
        args = (self.dec.weights, cache.k, cache.v) + pre + (
            prev_toks,
            aj(last_t), aj(prev_col), aj(use_host), aj(override),
            aj(ids), aj(pos), aj(slots), aj(rseq), aj(rctx),
            aj(ucar), aj(tables), aj(temps), keys)
        if fused:
            args = args + (aj(eos),)
        try:
            if rich:
                any_rep = any(r.sampling.repetition_penalty != 1.0
                              for r in reqs_of.values()) \
                    or any(f[0].sampling.repetition_penalty != 1.0
                           for f in finals)
                if any_rep:
                    seen = np.zeros((W, vocab), bool)
                    for si, c in col_of.items():
                        req = reqs_of[si]
                        if req.sampling.repetition_penalty != 1.0:
                            seen[c, req.prompt] = True
                            if req.out_tokens:
                                seen[c,
                                     np.asarray(req.out_tokens)] = True
                    for req, _, t, c in finals:
                        if req.sampling.repetition_penalty != 1.0:
                            seen[c, req.prompt] = True
                    seen_dev = aj(seen)
                else:
                    seen_dev = self._zeros_seen(W, vocab)
                # structured decoding: per-COLUMN allowed-vocab masks
                # (decode columns and sampling finals; discarded cells
                # of a shared column are masked harmlessly)
                entries = [(c, reqs_of[si].allowed_mask)
                           for si, c in col_of.items()]
                entries += [(c, req.allowed_mask)
                            for req, _, _t, c in finals]
                allowed_dev = self._allowed_operand(W, entries)
                self.masked_decode_columns += sum(
                    1 for si, _c in col_of.items()
                    if reqs_of[si].allowed_mask is not None)
                if fused:
                    prog = self._ragged_ms_lora_rich_j if use_lora \
                        else self._ragged_ms_rich_j
                else:
                    prog = self._ragged_lora_rich_j if use_lora \
                        else self._ragged_rich_j
                toks, cache.k, cache.v = self._device_call(
                    "dispatch:ragged", prog, *args,
                    aj(top_ks), aj(top_ps), aj(reps), seen_dev,
                    aj(upd), allowed_dev)
            else:
                if fused:
                    prog = self._ragged_ms_lora_j if use_lora \
                        else self._ragged_ms_j
                else:
                    prog = self._ragged_lora_j if use_lora \
                        else self._ragged_j
                toks, cache.k, cache.v = self._device_call(
                    "dispatch:ragged", prog, *args)
        except _DispatchFailed as e:
            # the unified chunk is ONE program: every surviving request
            # riding it fails together, with a structured error — the
            # engine keeps serving (0-step slots awaiting collection
            # and unscheduled prefills are untouched)
            for rid, (req, epoch) in sched.items():
                if req.epoch == epoch and req.state in ("running",
                                                        "prefilling"):
                    self._fail_request(
                        req, f"ragged dispatch failed after retries: "
                             f"{e}")
            return False

        # post-dispatch bookkeeping: the scheduled prefill rows are now
        # DISPATCHED — bump the splice watermark, complete resumes (no
        # sampling final; decode restarts from the host-held last
        # token), clear pending-write registrations of finished finals
        for rid, (req, epoch) in sched.items():
            take = take_of.get(rid, 0)
            if take and req.state == "prefilling":
                req.prefill_sent += take
                if req.prefill_sent >= req.suffix_len:
                    if req.resume:
                        self._resume_complete(req)
                    else:
                        self._clear_pending_writes(req)
        if self.tracer is not None:
            # k + decode_toks feed trace_report's dispatch-
            # amortization table (tokens scheduled per program launch,
            # split by fused-window depth)
            self.tracer.event(
                "dispatch", pid=self.replica_id, kind="ragged",
                T=int(T), W=int(W), decode_cols=len(col_of),
                prefill_rows=int(sum(take_of.values())),
                finals=len(finals),
                k=int(self.multi_step if fused else 1),
                decode_toks=int(sum(steps_of.values())))
        self._ph.set(dispatch=self._dispatch_seq, T=int(T), W=int(W),
                     decode_cols=len(col_of),
                     prefill_tokens=int(sum(take_of.values())))
        self._inflight.append({
            "kind": "ragged", "seq": self._dispatch_seq,
            "toks": toks, "T": T, "W": W,
            "cols": dict(col_of), "steps": dict(steps_of),
            "reqs": dict(reqs_of), "epochs": dict(epochs_of),
            "finals": list(finals),
            "real_rows": sum(take_of.values()),
            "k": self.multi_step if fused else 1,
            "free_after": []})
        return True

    def _collect_ragged(self, ch):
        """Fetch and process one collected ragged chunk: decode columns
        deliver up to `steps` tokens (epoch-guarded, mid-chunk EOS cut),
        sampling-final rows deliver their request's first token
        (completing the prefill), mid-chunk prefill rows carry no
        result. ITL attribution matches the dense path."""
        # THE designed blocking point of the ragged pipeline, in
        # device program order (retried on transient fetch faults)
        toks, e = self._fetch("engine.collect", "collect:ragged",
                              np.asarray, ch["toks"], ch)
        if e is not None:
            for si, steps in ch["steps"].items():
                req = ch["reqs"][si]
                if steps > 0 and req.state == "running" \
                        and req.epoch == ch["epochs"].get(si) \
                        and self._slots[si] is req:
                    self._fail_request(
                        req, f"chunk collection failed after retries: "
                             f"{e}")
            for req, epoch, _, _ in ch["finals"]:
                if req.state == "prefilling" and req.epoch == epoch:
                    self._fail_request(
                        req, f"prefill collection failed after "
                             f"retries: {e}")
            for rid in ch["free_after"]:
                self.dec.cache.free(rid)
            return
        with self._phase("engine.deliver", dispatch=ch["seq"]):
            self._deliver_ragged(ch, toks)

    def _deliver_ragged(self, ch, toks):
        """Token bookkeeping of one fetched ragged chunk."""
        now = time.perf_counter()
        self.decode_steps += ch["T"]
        # ragged utilization accounting: the program ran T x W cells
        # (T is the WINDOW length k*T under multi_step — entry "T"
        # carries the per-iteration row count, so tokens_per_dispatch
        # and padded_token_waste stay honest per ministep); useful
        # work = delivered decode tokens + real prefill rows, so
        # padded_token_waste is the true pad-to-grid remainder (plus
        # genuine post-EOS discards) — no scratch-slot steady waste
        self.decode_slot_steps += ch["T"] * ch["W"]
        self.decode_useful_tokens += ch["real_rows"]
        for si, steps in ch["steps"].items():
            req = ch["reqs"][si]
            if req.state != "running" \
                    or req.epoch != ch["epochs"].get(si):
                continue   # retired/preempted while the chunk flew
            c = ch["cols"][si]
            delivered = 0
            for t in range(steps):
                tok = int(toks[t, c])
                req.out_tokens.append(tok)
                delivered += 1
                self.generated_tokens += 1
                self._last_tok[si] = tok
                if self._is_finished(req):
                    break      # mid-chunk EOS: discard the tail
            fin = self._is_finished(req)
            if fin and delivered < steps and ch.get("k", 1) > 1:
                # the in-window EOS froze this column: the remaining
                # scheduled ministeps ran as scratch-aimed no-ops —
                # count them so the fused path's waste is honest
                self.ms_frozen_token_waste += steps - delivered
            self.decode_useful_tokens += delivered
            self._note_itl(req, now, delivered)
            if fin and self._slots[si] is req:
                self._retire(si)
        for req, epoch, t, c in ch["finals"]:
            if req.state != "prefilling" or req.epoch != epoch:
                continue
            si = req.slot
            tok = int(toks[t, c])
            req.state = "running"
            if self.tracer is not None:
                self._trace_running(req, now)
            self._mark_first_token(req, now)
            req.out_tokens.append(tok)
            req.planned = 1
            self.generated_tokens += 1
            self._last_tok[si] = tok
            self._fresh_slots.add(si)
            if self._is_finished(req):
                self._retire(si)
        for rid in ch["free_after"]:
            self.dec.cache.free(rid)

    def _collect_spec(self, ch):
        """Fetch and process one speculative verify chunk: per verify
        window, count the accepted prefix off the in-program mask,
        deliver accepted drafts + the bonus token (EOS / budget cut
        mid-window like any decode chunk), and ROLL the allocator BACK
        past the delivered tokens — the rejected tail's slots return
        so the next extend re-issues and overwrites them. Final
        prefill rows deliver their first token exactly like the ragged
        chunk's."""
        cache = self.dec.cache
        # the spec pipeline's designed blocking point (sync by
        # construction — acceptance decides the next schedule);
        # one batched fetch for tokens + accepted mask
        fetched, e = self._fetch("engine.collect", "collect:spec",
                                 jax.device_get, [ch["toks"], ch["acc"]],
                                 ch)
        if e is not None:
            for si, ent in ch["spec"].items():
                req = ent["req"]
                if req.state == "running" \
                        and req.epoch == ent["epoch"] \
                        and self._slots[si] is req:
                    self._fail_request(
                        req, f"spec collection failed after retries: "
                             f"{e}")
            for req, epoch, _ in ch["finals"]:
                if req.state == "prefilling" and req.epoch == epoch:
                    self._fail_request(
                        req, f"prefill collection failed after "
                             f"retries: {e}")
            for rid in ch["free_after"]:
                cache.free(rid)
            return
        with self._phase("engine.deliver", dispatch=ch["seq"]):
            self._deliver_spec(ch, np.asarray(fetched[0]),
                               np.asarray(fetched[1]))

    def _deliver_spec(self, ch, toks, acc):
        """Token bookkeeping and rollback of one fetched verify chunk."""
        cache = self.dec.cache
        now = time.perf_counter()
        self.decode_steps += 1
        self.decode_slot_steps += ch["W"]
        self.decode_useful_tokens += ch["real_rows"]
        for si, ent in ch["spec"].items():
            req = ent["req"]
            if req.state != "running" or req.epoch != ent["epoch"] \
                    or self._slots[si] is not req:
                continue   # retired/preempted while the chunk flew
            base, k, ctx0 = ent["base"], ent["k"], ent["ctx0"]
            m = 0
            while m < k and acc[base + 1 + m]:
                m += 1
            self.drafted_tokens += k
            self.accepted_draft_tokens += m
            if k:
                # per-window acceptance EMA (alpha 0.1): the adaptive-
                # window signal (ROADMAP 2), sampled into the
                # acceptance_ema counter track each step
                self.draft_acceptance_ema += 0.1 * (
                    m / k - self.draft_acceptance_ema)
            if m < k:
                self.spec_rollbacks += 1
            if self.tracer is not None and k:
                self.tracer.event(
                    "spec_window", trace=req.trace_id,
                    pid=self.replica_id, drafted=int(k),
                    accepted=int(m))
            delivered = 0
            for j in range(m + 1):
                tok = int(toks[base + j])
                req.out_tokens.append(tok)
                delivered += 1
                self.generated_tokens += 1
                self._last_tok[si] = tok
                if self._is_finished(req):
                    break      # EOS cut mid-draft-window
            self.decode_useful_tokens += delivered
            # sync collection: with nothing in flight, dispatched ==
            # delivered is the planned invariant (the window's
            # rejected remainder was never "planned work")
            req.planned = len(req.out_tokens)
            self._note_itl(req, now, delivered)
            if self._drafter is not None and k:
                self._drafter.observe(
                    np.concatenate(
                        [req.prompt,
                         np.asarray(req.out_tokens, np.int32)]),
                    m, k)
            if self._is_finished(req) and self._slots[si] is req:
                self._retire(si)
            else:
                # position/KV rollback: context length snaps to
                # exactly the KV the delivered prefix wrote (the
                # bonus token's KV is NOT written — it is the next
                # step's input like any freshly sampled token).
                # min_blocks: only blocks the window's own extends
                # appended may drop — never the admission reservation
                cache.rollback(req.req_id, ctx0 + delivered,
                               min_blocks=ent["tbl0"])
        for req, epoch, c in ch["finals"]:
            if req.state != "prefilling" or req.epoch != epoch:
                continue
            si = req.slot
            tok = int(toks[c])
            req.state = "running"
            if self.tracer is not None:
                self._trace_running(req, now)
            self._mark_first_token(req, now)
            req.out_tokens.append(tok)
            req.planned = 1
            self.generated_tokens += 1
            self._last_tok[si] = tok
            self._fresh_slots.add(si)
            if self._is_finished(req):
                self._retire(si)
        for rid in ch["free_after"]:
            cache.free(rid)

    def _note_itl(self, req: Request, now: float, delivered: int):
        """Per-token ITL attribution at collection, shared by the
        decode/ragged/spec collect paths: the chunk's wall interval
        split evenly over the tokens it delivered to this request
        (recorded on the request; mirrored into the engine.itl_s
        fixed-bucket histogram when tracing is on)."""
        if not delivered:
            return
        if req.t_last_emit is not None:
            itl = (now - req.t_last_emit) / delivered
            req.itls.extend([itl] * delivered)
            if self.tracer is not None:
                self.tracer.metrics.histogram(
                    "engine.itl_s").observe(itl, n=delivered)
            if self._slo is not None:
                # one weighted append per chunk — the SLO windows see
                # every delivered token without a per-token append
                self._slo.observe("itl", itl, self._slo_attrs(req),
                                  n=delivered, now=now)
        req.t_last_emit = now

    def _collect_oldest(self):
        """Fetch and process the oldest in-flight chunk — prefill or
        decode (the only host-blocking points of the engine). Mid
        prefill chunks carry no result and cost no fetch; final
        prefill chunks deliver the first token; decode chunks deliver
        T tokens per live slot and are timestamped here for the ITL
        accounting (the chunk's wall interval is attributed evenly
        over the tokens it delivered to each request)."""
        ch = self._inflight.popleft()
        if ch["kind"] == "spec":
            self._collect_spec(ch)
            return
        if ch["kind"] == "ragged":
            self._collect_ragged(ch)
            return
        if ch["kind"] == "prefill":
            if ch["toks"] is not None:
                # THE designed blocking point for a lone prefill
                # entry (runs of >1 batch through
                # _collect_prefill_run); retried on transient fetch
                # faults — a fetch never consumes the device buffer
                toks, e = self._fetch(
                    "engine.prefill_collect", "collect:prefill",
                    np.asarray, ch["toks"], ch)
                if e is not None:
                    self._fail_prefill_group(ch["group"], e)
                    for rid in ch["free_after"]:
                        self.dec.cache.free(rid)
                    return
                with self._phase("engine.deliver"):
                    self._prefill_complete(toks, ch["group"])
            for rid in ch["free_after"]:
                self.dec.cache.free(rid)
            return
        # THE designed blocking point of the decode pipeline:
        # collection fetches the oldest in-flight chunk, in device
        # program order (retried on transient fetch faults)
        toks, e = self._fetch("engine.collect", "collect:decode",
                              np.asarray, ch["toks"], ch)
        if e is not None:
            for si, steps in ch["steps"].items():
                req = ch["reqs"][si]
                if steps > 0 and req.state == "running" \
                        and req.epoch == ch["epochs"].get(si) \
                        and self._slots[si] is req:
                    self._fail_request(
                        req, f"chunk collection failed after retries: "
                             f"{e}")
            for rid in ch["free_after"]:
                self.dec.cache.free(rid)
            return
        with self._phase("engine.deliver", dispatch=ch["seq"]):
            self._deliver_decode(ch, toks)

    def _deliver_decode(self, ch, toks):
        """Token bookkeeping of one fetched dense decode chunk."""
        now = time.perf_counter()
        self.decode_steps += ch["T"]
        self.decode_slot_steps += ch["T"] * self.max_b
        for si, steps in ch["steps"].items():
            req = ch["reqs"][si]
            if req.state != "running" \
                    or req.epoch != ch["epochs"].get(si):
                continue   # retired/preempted while the chunk flew
            delivered = 0
            for t in range(steps):
                tok = int(toks[si, t])
                req.out_tokens.append(tok)
                delivered += 1
                self.generated_tokens += 1
                self._last_tok[si] = tok
                if self._is_finished(req):
                    break      # mid-chunk EOS: discard the tail
            self.decode_useful_tokens += delivered
            self._note_itl(req, now, delivered)
            if self._is_finished(req) and self._slots[si] is req:
                self._retire(si)
        for rid in ch["free_after"]:
            self.dec.cache.free(rid)

    def _collect_prefill_run(self, n: int):
        """Collect `n` CONSECUTIVE leading prefill entries with ONE
        batched device_get: every blocking fetch stalls the host on the
        device queue, so a 16-request burst over 4 final groups pays
        it once, not once per group — the chunk pipeline's analog of
        the batched fetch the old blocking admission used. No-sample
        mid entries carry no result and are skipped by the fetch."""
        chs = [self._inflight.popleft() for _ in range(n)]
        fetch = [ch["toks"] for ch in chs if ch["toks"] is not None]
        # designed batched fetch: one blocking fetch per prefill run
        # (retried whole on transient faults — fetches never consume
        # device buffers)
        fetched, e = (self._fetch(
            "engine.prefill_collect", "collect:prefill", jax.device_get,
            fetch) if fetch else ([], None))
        if e is not None:
            for ch in chs:
                if ch["toks"] is not None:
                    self._fail_prefill_group(ch["group"], e)
                for rid in ch["free_after"]:
                    self.dec.cache.free(rid)
            return
        it = iter(fetched)
        with self._phase("engine.deliver"):
            for ch in chs:
                if ch["toks"] is not None:
                    # re-wrap of the batched fetch above (already host
                    # memory — the sync was paid at the designed point)
                    self._prefill_complete(np.asarray(next(it)),  # flightcheck: disable=FC301
                                           ch["group"])
                for rid in ch["free_after"]:
                    self.dec.cache.free(rid)

    def _fail_prefill_group(self, group, e: Exception):
        """Fail every request of an uncollectable final-prefill entry
        that is still waiting on it (epoch guard: requests restarted
        since the dispatch are someone else's problem now)."""
        for si, req, epoch in group:
            if req.state == "prefilling" and req.epoch == epoch:
                self._fail_request(
                    req, f"prefill collection failed after retries: {e}")

    def step(self) -> bool:
        """One engine iteration: admit, dispatch budget-bounded prefill
        chunks, dispatch the next decode chunk, then collect down to
        the pipeline depth (1 chunk stays in flight in overlap mode, so
        host admission/bookkeeping runs while the device decodes; the
        newest entry is the decode chunk whenever one was dispatched,
        so prefill results are always collected by the end of the step
        that could consume them). Returns True while there is still
        work. Fault tolerance: deadline enforcement runs first (an
        expired request never costs another dispatch); dispatch/fetch
        errors and KV pressure are absorbed inside the phases — step()
        itself never raises on a per-request fault."""
        self._step_seq += 1
        with self._phase("engine.step", step=self._step_seq):
            return self._step()

    def _step(self) -> bool:
        with self._phase("engine.deadlines"):
            self._enforce_deadlines()
        with self._phase("engine.admit"):
            self._admit()
        if self.ragged:
            # unified ragged path: decode AND prefill rows ride ONE
            # device program per step (no separate prefill dispatches,
            # no merge dispatch)
            dispatched = self._dispatch_ragged()
        else:
            self._dispatch_prefill()
            dispatched = self._dispatch_chunk()
        # a speculative verify chunk is always collected THIS step
        # (depth 0): its accepted count decides the next schedule —
        # positions, slots and drafts — so there is nothing correct
        # to pipeline behind it
        depth = 1 if (dispatched and self.overlap
                      and not self._rep_active()
                      and not any(e["kind"] == "spec"
                                  for e in self._inflight)) else 0
        while len(self._inflight) > depth:
            # a RUN of leading prefill entries is fetched with one
            # batched device_get (one blocking fetch per burst, not per
            # group); decode entries collect singly
            n = 0
            while (n < len(self._inflight) - depth
                   and self._inflight[n]["kind"] == "prefill"):
                n += 1
            if n > 1:
                self._collect_prefill_run(n)
            else:
                self._collect_oldest()
        if self.tracer is not None:
            # counter tracks (ISSUE 14): sample the scheduler gauges
            # into the trace every step so Perfetto renders resource
            # timelines next to the request spans
            self._sample_counter_tracks()
        if self._debug_pool:
            # PADDLE_TPU_POOL_DEBUG=1: assert the pool invariant
            # (free + cached + referenced == num_blocks, refs == table
            # contents, partial-prefill length bounds) after every
            # scheduler step — including between the chunks of a
            # multi-step prefill. With a lora registry, the adapter-
            # page invariants (use counts vs slots, page refs/hashes,
            # no zero-use allocations) ride the same sweep.
            self.dec.cache.debug_check()
            if self.lora is not None:
                self._debug_lora_check()
        return self.has_work

    def _sample_counter_tracks(self):
        """One sample per scheduler gauge per step (tracer attached):
        exported as Perfetto ``ph:"C"`` counter events, latest values
        mirrored as ``track.*`` registry gauges. Host scheduler state
        only — no device read, no schedule change."""
        tr = self.tracer
        pid = self.replica_id
        cache = self.dec.cache
        tr.counter("running_slots",
                   sum(1 for r in self._slots
                       if r is not None and r.state == "running"), pid)
        tr.counter("queue_depth", len(self._queue), pid)
        tr.counter("inflight_chunks", len(self._inflight), pid)
        tr.counter("free_blocks", cache.free_blocks, pid)
        tr.counter("cached_blocks", cache.cached_blocks, pid)
        if self.spec is not None:
            tr.counter("acceptance_ema", self.draft_acceptance_ema,
                       pid)
        if self.lora is not None:
            tr.counter("active_adapters", self.lora.active_count(),
                       pid)

    def run_to_completion(self) -> Dict[int, np.ndarray]:
        """Drain the queue; returns {req_id: generated tokens}."""
        while self.step():
            pass
        return {rid: self.result(rid) for rid in list(self._done)}

    def warmup(self, prompt_len: Optional[int] = None,
               seal_programs: bool = False):
        """Pre-compile the serving programs — BOTH prefill widths for
        every bucket (or just prompt_len's bucket when given), the
        prefix-cache HIT prefill for every hit-reachable suffix bucket,
        plus the decode chunk — with throwaway requests, so no user
        request pays a compile. ``seal_programs=True`` additionally
        compiles the full reachable program grid (warmup_programs) and
        SEALS the set, so any later retrace counts as
        unexpected_recompiles (bound ragged_idle_cap first on ragged
        engines, or the grid is large). Prompts longer than prefill_chunk run
        the CHUNKED path (exactly as production traffic at that length
        will), compiling the no-sample chunk programs and the
        remainder-bucket finals instead of the monolithic full-length
        variants. Worth calling once at deployment; finished-request
        stats AND the prefix cache are cleared afterwards. Warns if
        the KV pool is too small to exercise the burst width (that
        variant would then compile on the first real burst)."""
        import warnings as _warnings
        plens = ([prompt_len] if prompt_len is not None
                 else list(self.buckets))
        cache = self.dec.cache
        width = min(self.PREFILL_GROUP, self.max_b)
        if self.max_b < 2:
            _warnings.warn(
                "warmup: max_batch_size < 2 — the burst prefill path "
                "never runs on this engine; only width-1 is warmed")
        for plen in plens:
            # phase 1: a single request — the width-1 program(s); a
            # plen past the chunk size compiles the chunk ladder
            self.add_request(self._warmup_prompt(plen),
                             SamplingParams(max_new_tokens=2))
            self.run_to_completion()
            if self.max_b < 2:
                continue
            # phase 2: a burst — the width-`width` program. The burst
            # path only runs if >= 2 requests admit TOGETHER.
            need = 2 * -(-(plen + 2) // cache.block_size)
            if cache.available_blocks < need:
                _warnings.warn(
                    f"warmup: pool too small to exercise the width-"
                    f"{width} prefill at bucket {plen} (need {need} "
                    "free pages); the first real burst there will pay "
                    "that compile")
                continue
            for _ in range(width):
                self.add_request(self._warmup_prompt(plen),
                                 SamplingParams(max_new_tokens=2))
            self.run_to_completion()
        # prefix-cache HIT programs: the suffix-prefix prefill compiles
        # per (suffix bucket, width), and warmup's distinct-fill miss
        # traffic never runs it — seed a one-block prefix, then admit
        # hits whose suffix lands in each reachable bucket (width 1),
        # plus one burst at the first reachable bucket (width `width`).
        # Suffixes past prefill_chunk take the chunked path here too,
        # warming the offset chunk program a long cache hit runs.
        if self.prefix_caching:
            bs = cache.block_size
            prefix = self._warmup_prompt(bs)
            seeded = burst_done = False

            def _hit_round(s_suf, rows):
                for _ in range(rows):
                    self.add_request(
                        np.concatenate([prefix,
                                        self._warmup_prompt(s_suf)]),
                        SamplingParams(max_new_tokens=2))
                self.run_to_completion()

            for b in self.buckets:
                s_suf = min(b, self.buckets[-1] - bs)
                if s_suf <= 0 or _bucket_for(s_suf, self.buckets) != b:
                    continue   # no runtime hit can land in this bucket
                per_hit = -(-(bs + s_suf + 2) // bs)
                if cache.available_blocks < per_hit + 1:
                    _warnings.warn(
                        f"warmup: pool too small to warm the prefix-hit "
                        f"prefill at suffix bucket {b}; the first real "
                        "hit there will pay that compile")
                    continue
                if not seeded:
                    # park the shared prefix block (suffix of 1 token)
                    self.add_request(
                        np.concatenate([prefix, self._warmup_prompt(1)]),
                        SamplingParams(max_new_tokens=1))
                    self.run_to_completion()
                    seeded = True
                _hit_round(s_suf, 1)
                if not burst_done and self.max_b >= 2 and \
                        cache.available_blocks >= width * per_hit:
                    _hit_round(s_suf, width)
                    burst_done = True
        # rich-sampling + plain decode programs, once per ladder chunk
        # size (each T is its own compiled program): top_k=1 is greedy,
        # so the rich throwaway is deterministic but routes through
        # _decode_rich_j. Spanning MULTIPLE decode chunks also compiles
        # the overlap-mode _merge_first_j chunk-to-chunk gather.
        warmed_rungs = set()
        for c in self.chunks:
            if -(-(plens[0] + c + 2) // cache.block_size) > \
                    cache.available_blocks:
                _warnings.warn(
                    f"warmup: pool too small to warm chunk rung {c}; "
                    f"its first real dispatch will pay the compile")
                continue
            warmed_rungs.add(c)
            # pin the rung: the heuristic could skip a middle rung whose
            # budget lands on a bigger one (its compile would then leak
            # into the timed cost loop below)
            self._force_chunk = c
            try:
                self.add_request(self._warmup_prompt(plens[0]),
                                 SamplingParams(max_new_tokens=c + 2,
                                                temperature=1.0,
                                                top_k=1))
                self.run_to_completion()
                self.add_request(self._warmup_prompt(plens[0]),
                                 SamplingParams(max_new_tokens=c + 2))
                self.run_to_completion()
            finally:
                self._force_chunk = None
        # measure each rung's steady chunk cost (compiles are done):
        # one request pinned to rung c for 3 chunks; the stall+host
        # delta over 3 chunks is the per-chunk cost _pick_chunk's
        # tokens/cost policy uses
        if len(self.chunks) > 1:
            for c in self.chunks:
                if c not in warmed_rungs:
                    # never time an un-warmed rung: the measurement
                    # would absorb its XLA compile and the rate policy
                    # would shun the rung forever
                    continue
                # clamp the measurement to the pool: a production pool
                # sized for small budgets must not fail warmup. Prefer
                # 3 chunks; fall back to fewer; skip the rung (leaving
                # it out of the cost table) if even one doesn't fit.
                n_chunks = 3
                while n_chunks > 0:
                    need = -(-(plens[0] + n_chunks * c)
                             // cache.block_size)
                    if need <= cache.available_blocks:
                        break
                    n_chunks -= 1
                if n_chunks == 0:
                    _warnings.warn(
                        f"warmup: pool too small to measure chunk rung "
                        f"{c} (needs {-(-(plens[0] + c) // cache.block_size)} "
                        f"free pages); rung left uncosted — the rate "
                        f"policy will not select it")
                    continue
                self._force_chunk = c
                try:
                    before = self.time_stall_s + self.time_host_s
                    self.add_request(
                        self._warmup_prompt(plens[0]),
                        SamplingParams(max_new_tokens=n_chunks * c))
                    self.run_to_completion()
                    delta = (self.time_stall_s + self.time_host_s
                             - before)
                finally:
                    self._force_chunk = None
                self._chunk_cost[c] = max(delta / n_chunks, 1e-6)
        # multi-tenant warmup (ISSUE 10): one short adapter-carrying
        # request compiles the lora ragged program family so the first
        # real tenant request pays no compile (base-only programs were
        # warmed above; an all-base dispatch never runs the lora
        # variant)
        if self.lora is not None and self.lora.ids():
            aid = self.lora.ids()[0]
            need = self.lora.n_pages() \
                + -(-(plens[0] + 2) // cache.block_size)
            if cache.available_blocks < need:
                _warnings.warn(
                    "warmup: pool too small to warm the lora serving "
                    "program; the first tenant request will pay that "
                    "compile")
            else:
                self.add_request(
                    self._warmup_prompt(plens[0]),
                    SamplingParams(max_new_tokens=2, adapter_id=aid))
                self.run_to_completion()
        # warmup traffic must leave no trace: parked throwaway blocks
        # would otherwise occupy LRU slots (and could in principle be
        # spliced by a real request with the same fill pattern) —
        # clear_prefix_cache also evicts warmup's parked adapter pages
        cache.clear_prefix_cache()
        if seal_programs:
            # close the remaining grid (rungs/widths the throwaway
            # traffic didn't reach) and declare the set sealed — from
            # here a mid-serving retrace is a counted, assertable bug
            self.warmup_programs()
            self.seal_programs()
        self.clear_finished()

    # -- program observatory: grid warmup + sealing (ISSUE 14) ---------------
    def reachable_ragged_widths(self, T: int,
                                max_width: Optional[int] = None
                                ) -> List[int]:
        """The W rungs a T-ministep ragged program can be dispatched
        at, derived from engine config: mixed-regime chunks carry at
        most max_b decode columns plus ceil(prefill_budget / T)
        prefill columns; pure-prefill chunks widen to the idle cap.
        Sticky-shrink only ever pads to a previously-reached width at
        the same T, so this set is CLOSED — compiling it whole is what
        makes seal_programs assertable."""
        cap = self._ragged_cap
        idle = max(cap, self._ragged_idle_cap)
        rows = max(self.max_b + -(-cap // T), -(-idle // T))
        return self._widths_up_to(rows, max_width)

    def _widths_up_to(self, rows: int,
                      max_width: Optional[int] = None) -> List[int]:
        """W rungs (the static ladder, then 64-multiples) reachable up
        to the padded width of ``rows`` — shared by the ragged and spec
        grids so the ladder/rounding rule can never drift between them
        (a one-sided change would make warmup_programs' grids disagree
        and seed sealed-set false positives)."""
        if max_width is not None:
            rows = min(rows, int(max_width))
        bound = self._ragged_width(rows)
        widths = [w for w in self.RAGGED_WIDTHS if w <= bound]
        w = (widths[-1] if widths else 0) + 64
        w -= w % 64
        while w <= bound:           # past-ladder 64-multiples
            widths.append(w)
            w += 64
        return widths

    def _spec_widths(self, max_width: Optional[int] = None
                     ) -> List[int]:
        """Reachable W rungs of the one-ministep speculative verify
        program: every running column fans out to 1 + draft_len rows,
        prefill rows fill what is left of the per-step budget."""
        rows = self.max_b * (1 + self.spec.draft_len) + self._ragged_cap
        return self._widths_up_to(rows, max_width)

    def warmup_programs(self, max_width: Optional[int] = None):
        """Compile the reachable serving-program grid by DIRECT
        program invocation — dummy operands aimed entirely at the
        scratch page/row, so no scheduler state changes, no pool block
        is claimed, and (unlike traffic-driven warmup) NO engine PRNG
        key is consumed: a warmed engine serves token-identical to an
        unwarmed one, stochastic sampling included. Every call routes
        through CompileWatch.observe, so the compiles land in the
        trace as compile spans; afterwards seal_programs() can declare
        the set closed. ``max_width`` clamps the ragged W rungs (tests
        use it to leave a rung cold on purpose)."""
        cache = self.dec.cache
        weights = self.dec.weights
        mb, mp, vocab = self.max_b, self.dec.max_pages, \
            self.dec.cfg.vocab_size
        aj = self._aj
        key1 = self._replicated(jax.random.PRNGKey(0))

        def obs(fn, *args):
            t0 = time.perf_counter()
            out = fn(*args)
            n_new, n_unexp = self.compile_watch.observe(
                fn, t0, time.perf_counter(), args)
            self.program_compiles += n_new
            self.unexpected_recompiles += n_unexp
            return out

        if not self.ragged:
            # dense per-phase programs: final prefill (plain + prefix
            # splice) per (bucket, width), the no-sample mid-chunk
            # ladder, the decode chunk rungs (+ rich twins) and the
            # overlap merge
            widths = sorted({1, min(self.PREFILL_GROUP, self.max_b)})
            for b in self.buckets:
                for w in widths:
                    ids = aj(np.zeros((w, b), np.int32))
                    slots = aj(np.full((w, b), self._scratch_slot,
                                       np.int32))
                    last_idx = aj(np.zeros(w, np.int32))
                    temps = aj(np.zeros(w, np.float32))
                    tks = aj(np.zeros(w, np.int32))
                    tps = aj(np.ones(w, np.float32))
                    reps = aj(np.ones(w, np.float32))
                    seen = self._zeros_seen(w, vocab)
                    allowed = self._ones_allowed(w, vocab)
                    _, cache.k, cache.v = obs(
                        self._prefill_j, weights, cache.k, cache.v,
                        ids, slots, last_idx, temps, key1, tks, tps,
                        reps, seen, allowed)
                    ncv = aj(np.zeros(w, np.int32))
                    ptab = aj(np.full((w, self._prefix_pages),
                                      self._scratch_block, np.int32))
                    _, cache.k, cache.v = obs(
                        self._prefill_prefix_j, weights, cache.k,
                        cache.v, ids, slots, last_idx, ncv, ptab,
                        temps, key1, tks, tps, reps, seen, allowed)
            if self._can_recompute:
                c = self.prefill_chunk or self._recompute_chunk
                ids1 = aj(np.zeros((1, c), np.int32))
                slots1 = aj(np.full((1, c), self._scratch_slot,
                                    np.int32))
                cache.k, cache.v = obs(self._prefill_mid0_j, weights,
                                       cache.k, cache.v, ids1, slots1)
                for pb in self._prefix_page_buckets:
                    ptab = aj(np.full((1, pb), self._scratch_block,
                                      np.int32))
                    cache.k, cache.v = obs(
                        self._prefill_mid_j, weights, cache.k, cache.v,
                        ids1, slots1, aj(np.asarray([1], np.int32)),
                        ptab)
            for T in self.chunks:
                first = aj(np.zeros(mb, np.int32))
                tables = aj(np.full((T, mb, mp), self._scratch_block,
                                    np.int32))
                ctx = aj(np.zeros((T, mb), np.int32))
                slots = aj(np.full((T, mb), self._scratch_slot,
                                   np.int32))
                temps = aj(np.zeros(mb, np.float32))
                keys = jax.random.split(jax.random.PRNGKey(0), T)
                toks, cache.k, cache.v = obs(
                    self._decode_j, weights, cache.k, cache.v, first,
                    tables, ctx, slots, temps, keys)
                obs(self._merge_first_j, toks, aj(np.zeros(mb,
                    np.int32)), aj(np.zeros(mb, np.int32)),
                    aj(np.ones(mb, bool)))
                _, cache.k, cache.v = obs(
                    self._decode_rich_j, weights, cache.k, cache.v,
                    first, tables, ctx, slots, temps, keys,
                    aj(np.zeros(mb, np.int32)),
                    aj(np.ones(mb, np.float32)),
                    aj(np.ones(mb, np.float32)),
                    self._zeros_seen(mb, vocab),
                    self._ones_allowed(mb, vocab))
            return

        # ragged grid: every (T, W) variant of the unified chunk (+
        # rich and lora twins where configured), then the spec verify
        # widths. All rows are scratch rows (rctx 0), exactly the
        # schedule shape an all-neutralized production chunk ships.
        scratch_row = mb
        lora_pre = ()
        if self.lora is not None:
            lora_pre = (cache.lora_pool, self._shard_ids,
                        aj(np.full((mb + 1, self.lora.n_pages()),
                                   self._scratch_block, np.int32)))
        def ragged_tail(T, W):
            z2 = np.zeros((T, W), np.int32)
            return (self._zeros_toks(T, W),
                    aj(np.zeros(W, np.int32)),
                    aj(np.zeros(W, np.int32)),
                    aj(np.ones(W, bool)),
                    aj(np.zeros(W, np.int32)),
                    aj(z2), aj(z2),
                    aj(np.full((T, W), self._scratch_slot, np.int32)),
                    aj(np.full((T, W), scratch_row, np.int32)),
                    aj(z2),
                    aj(np.zeros((T, W), bool)),
                    aj(np.full((mb + 1, mp), self._scratch_block,
                               np.int32)),
                    aj(np.zeros((T, W), np.float32)),
                    self._replicated(
                        jax.random.split(jax.random.PRNGKey(0), T)))

        def ragged_rich_tail(T, W):
            return (aj(np.zeros((T, W), np.int32)),
                    aj(np.ones((T, W), np.float32)),
                    aj(np.ones((T, W), np.float32)),
                    self._zeros_seen(W, vocab),
                    aj(np.zeros(W, bool)),
                    self._ones_allowed(W, vocab))

        for T in sorted(set(list(self.chunks) + [1])):
            for W in self.reachable_ragged_widths(T, max_width):
                tail = ragged_tail(T, W)
                _, cache.k, cache.v = obs(
                    self._ragged_j, weights, cache.k, cache.v, *tail)
                rich_tail = ragged_rich_tail(T, W)
                _, cache.k, cache.v = obs(
                    self._ragged_rich_j, weights, cache.k, cache.v,
                    *tail, *rich_tail)
                if self.lora is not None:
                    _, cache.k, cache.v = obs(
                        self._ragged_lora_j, weights, cache.k,
                        cache.v, *lora_pre, *tail)
                    _, cache.k, cache.v = obs(
                        self._ragged_lora_rich_j, weights, cache.k,
                        cache.v, *lora_pre, *tail, *rich_tail)
        if self.multi_step > 1:
            # the (T, W, k) grid (ISSUE 16): fused windows dispatch at
            # k x the chunk rung picked over running slots, and only
            # in the pure-decode regime — but sticky-shrink can pad a
            # window up to ANY width the same window length reached
            # (including a prefill-widened single-step chunk when
            # k*chunk collides with a chunk rung), so the fused
            # families compile the full reachable width set per rung.
            # Scratch-aimed operands like the base grid; eos -1 = the
            # no-EOS schedule every all-neutralized window ships.
            for T in sorted({self.multi_step * c for c in self.chunks}):
                for W in self.reachable_ragged_widths(T, max_width):
                    tail = ragged_tail(T, W)
                    eos = aj(np.full(W, -1, np.int32))
                    _, cache.k, cache.v = obs(
                        self._ragged_ms_j, weights, cache.k, cache.v,
                        *tail, eos)
                    rich_tail = ragged_rich_tail(T, W)
                    _, cache.k, cache.v = obs(
                        self._ragged_ms_rich_j, weights, cache.k,
                        cache.v, *tail, eos, *rich_tail)
                    if self.lora is not None:
                        _, cache.k, cache.v = obs(
                            self._ragged_ms_lora_j, weights, cache.k,
                            cache.v, *lora_pre, *tail, eos)
                        _, cache.k, cache.v = obs(
                            self._ragged_ms_lora_rich_j, weights,
                            cache.k, cache.v, *lora_pre, *tail, eos,
                            *rich_tail)
        if self.spec is not None:
            for W in self._spec_widths(max_width):
                z1 = np.zeros(W, np.int32)
                spec_tail = (
                    aj(z1), aj(np.zeros(W, bool)), aj(z1), aj(z1),
                    aj(np.full(W, self._scratch_slot, np.int32)),
                    aj(np.full(W, scratch_row, np.int32)), aj(z1),
                    aj(np.full((mb + 1, mp), self._scratch_block,
                               np.int32)),
                    aj(np.zeros(W, np.float32)), key1,
                    aj(np.arange(W, dtype=np.int32)),
                    aj(np.zeros(W, bool)))
                _, _, cache.k, cache.v = obs(
                    self._spec_j, weights, cache.k, cache.v,
                    *spec_tail)
                if self.lora is not None:
                    _, _, cache.k, cache.v = obs(
                        self._spec_lora_j, weights, cache.k, cache.v,
                        *lora_pre, *spec_tail)

    def seal_programs(self):
        """Declare the compiled program set COMPLETE (call after
        warmup_programs, or after a steady-state lap whose program set
        is the production one): from here on, any compile observed by
        the watch increments stats()["unexpected_recompiles"] and
        fires an ``unexpected_recompile`` tracer event — the runtime
        FC2xx. The chaos legs assert zero."""
        self.compile_watch.seal()

    def clear_finished(self):
        """Drop finished requests + counters (e.g. after warmup) so
        stats() reflect only the workload that follows — including the
        prefix-cache hit/eviction counters and the ITL/utilization
        accounting, so warmup traffic cannot pollute the reported
        numbers."""
        self._done.clear()
        self.decode_steps = 0
        self.generated_tokens = 0
        self.decode_slot_steps = 0
        self.decode_useful_tokens = 0
        self.time_prefill_s = 0.0
        self.time_stall_s = 0.0
        self.time_host_s = 0.0
        self.time_by_phase_s = {}
        # robustness counters reset alongside the prefix-cache ones so
        # a post-warmup stats() reflects only real traffic
        self.preemptions = 0
        self.recompute_tokens = 0
        self.aborted = 0
        self.failed = 0
        self.deadline_misses = 0
        self.shed_requests = 0
        self.retries = 0
        self.dispatch_exhaustions = 0
        self.device_dispatches = 0
        self.drafted_tokens = 0
        self.accepted_draft_tokens = 0
        self.spec_rollbacks = 0
        # multi-tenant counters reset alongside everything else
        self.lora_dispatches = 0
        self.lora_rows = 0
        self.masked_decode_columns = 0
        # multi-step fused-decode counters (ISSUE 16); the multi_step
        # gauge itself is engine config and survives, like kv_quant
        self.ms_windows = 0
        self.ms_frozen_token_waste = 0
        # program-observatory counters (ISSUE 14): the engine-side
        # view resets with every other counter family; the
        # CompileWatch's own cumulative ledger (and its sealed flag)
        # survives — the program set is an engine property, not a
        # workload one
        self.unexpected_recompiles = 0
        self.program_compiles = 0
        self.profiled_dispatches = 0
        self.draft_acceptance_ema = 0.0
        if self._slo is not None:
            self._slo.reset()
        self._slo_violating.clear()
        # the memo keys masks by object identity; retained requests
        # (and their masks) are dropped here, so the memo must go too
        # (a recycled id must never alias a dead request's operand)
        self._allowed_memo.clear()
        # finished-request ITL reservoir resets with the requests it
        # sampled (same seed: identical runs keep identical stats)
        self._itl_res = Reservoir(self.ITL_RESERVOIR_K)
        if self.lora is not None:
            self.lora.reset_stats()
        self.dec.cache.reset_prefix_stats()

    def stats(self) -> dict:
        """Latency/throughput summary over finished requests.

        Timing keys:
        - latency/ttft percentiles: per-request wall clocks.
        - itl_p50_s / itl_p99_s: inter-token latency — each collected
          decode chunk's wall interval split evenly over the tokens it
          delivered to a request (chunks of T tokens arrive together;
          the per-token attribution is T-ths of the gap, the standard
          chunked-serving convention). The headline metric for
          chunked prefill: a long prompt admitted mid-stream must not
          spike running requests' ITL. Aggregated over successfully
          finished AND currently-running requests (aborted/failed
          lifetimes are excluded, like the other percentiles).
        - queue_wait_p50_s: submit → batch-slot admission.
        - time_prefill_s / time_decode_stall_s / time_host_s: wall
          time of the engine's blocking call sites. Prefill results
          are fetched at collection time in device order (never inside
          admission), so a prefill fetch waits only on work dispatched
          BEFORE it — the old overlap caveat (a blocking prefill fetch
          silently absorbing in-flight decode time) is gone; the one
          residual coupling is that the device runs a single queue, so
          the oldest entry's fetch covers any earlier entries still
          executing.

        Utilization keys (chunk-ladder tuning): a decode dispatch runs
        T steps x max_batch slots regardless of real work —
        padded_token_waste counts slot-steps that produced no delivered
        token (inactive slots, budget-drained tails, post-EOS
        discards), decode_utilization = delivered / slot-steps."""
        cache = self.dec.cache
        ok = [r for r in self._done.values() if r.state == "done"]
        lats = [r.latency_s for r in ok if r.latency_s is not None]
        ttfts = [r.ttft_s for r in ok if r.ttft_s is not None]
        waits = [r.queue_wait_s for r in ok
                 if r.queue_wait_s is not None]
        # terminal side filtered to state=="done" like lats/ttfts/waits
        # above: an aborted/failed request's stall-inflated gaps must
        # not bleed into the successful-traffic ITL percentiles.
        # Finished requests' samples come from the bounded reservoir
        # (fed at _retire — done-state lifetimes only); live slotted
        # requests' samples are read exactly. Exact below the reservoir
        # capacity, sampling-tolerance beyond it (ISSUE 12 satellite:
        # the raw union list grew without limit on long runs).
        itls = list(self._itl_res) + [
            x for r in self._slots if r is not None for x in r.itls]

        def pct(xs, p):
            # Interpolated (the truncating index form overstated
            # p50/p99 on small samples).
            return float(np.quantile(xs, p)) if xs else None

        out = {
            # finished = completed successfully; aborted/failed/shed
            # are accounted separately below (latency/TTFT percentiles
            # cover successful requests only — a deadline abort's
            # truncated lifetime must not flatter the percentiles)
            "finished": len(ok),
            # -- robustness counters (reset by clear_finished) --------
            "preemptions": self.preemptions,
            "recompute_tokens": self.recompute_tokens,
            "aborted": self.aborted,
            "failed": self.failed,
            "deadline_misses": self.deadline_misses,
            "shed_requests": self.shed_requests,
            "retries": self.retries,
            "dispatch_exhaustions": self.dispatch_exhaustions,
            "decode_steps": self.decode_steps,
            "generated_tokens": self.generated_tokens,
            "latency_p50_s": pct(lats, 0.50),
            "latency_p99_s": pct(lats, 0.99),
            "ttft_p50_s": pct(ttfts, 0.50),
            "ttft_p99_s": pct(ttfts, 0.99),
            "itl_p50_s": pct(itls, 0.50),
            "itl_p99_s": pct(itls, 0.99),
            "queue_wait_p50_s": pct(waits, 0.50),
            "time_prefill_s": self.time_prefill_s,
            "time_decode_stall_s": self.time_stall_s,
            "time_host_s": self.time_host_s,
            # the same seconds by the phase that spent them (each phase
            # feeds exactly one of the three above, so the values sum
            # to their sum); the phases are the engine's span names
            "time_by_phase_s": dict(self.time_by_phase_s),
            # device-program launches and delivered tokens per launch —
            # the ragged path's headline: one program per step instead
            # of merge + decode + N prefill dispatches. Accepted draft
            # tokens are generated_tokens like any other delivered
            # token, so speculative decoding's win shows up here
            # directly (a verify dispatch delivers up to draft_len + 1
            # tokens per column). Under multi_step=k a fused window is
            # ONE launch delivering up to k*T tokens per column —
            # decode_steps/slot_steps count its per-iteration rows
            # (entry "T" carries the window length), so this ratio and
            # the waste terms below stay per-ministep honest.
            "device_dispatches": self.device_dispatches,
            "tokens_per_dispatch": (
                self.generated_tokens / self.device_dispatches
                if self.device_dispatches else 0.0),
            # -- speculative decoding (reset by clear_finished) -------
            "drafted_tokens": self.drafted_tokens,
            "accepted_draft_tokens": self.accepted_draft_tokens,
            "draft_acceptance_rate": (
                self.accepted_draft_tokens / self.drafted_tokens
                if self.drafted_tokens else 0.0),
            "spec_rollbacks": self.spec_rollbacks,
            # -- multi-tenant LoRA serving (reset by clear_finished) --
            # active_adapters: adapters pinned by >= 1 slotted request
            # right now; hits/misses/evictions: registry residency
            # traffic (hit = ref-bump or LRU revive, miss = fault-in
            # upload, eviction = a previously-resident adapter found
            # evicted at re-acquire); lora_rows_per_dispatch: ragged
            # rows that carried a real adapter per lora dispatch — the
            # mixed-tenant batching density; masked_decode_columns:
            # scheduled decode columns under an allowed_tokens mask
            "active_adapters": (self.lora.active_count()
                                if self.lora is not None else 0),
            "adapter_cache_hits": (self.lora.hits
                                   if self.lora is not None else 0),
            "adapter_cache_misses": (self.lora.misses
                                     if self.lora is not None else 0),
            "adapter_cache_evictions": (
                self.lora.evictions if self.lora is not None else 0),
            "lora_rows_per_dispatch": (
                self.lora_rows / self.lora_dispatches
                if self.lora_dispatches else 0.0),
            "masked_decode_columns": self.masked_decode_columns,
            # -- multi-step fused decode (ISSUE 16) -------------------
            # multi_step_k: the engine's configured window depth (a
            # config gauge, like kv_quant — clear_finished leaves it);
            # multi_step_windows: fused windows dispatched;
            # ms_frozen_token_waste: slot-steps scheduled into fused
            # windows but frozen by an in-window EOS (a subset of
            # padded_token_waste — the honest cost of running EOS
            # bookkeeping on device instead of re-planning every step)
            "multi_step_k": float(self.multi_step),
            "multi_step_windows": self.ms_windows,
            "ms_frozen_token_waste": self.ms_frozen_token_waste,
            "decode_slot_steps": self.decode_slot_steps,
            # ragged-aware: on the ragged path slot_steps counts the
            # [T, W] grid actually dispatched (W sized by real rows)
            # and useful tokens include dispatched prefill rows, so
            # this is the true pad-to-grid remainder (plus post-EOS
            # discards) — the dense path's scratch-slot waste term is
            # structurally gone there
            "padded_token_waste": (self.decode_slot_steps
                                   - self.decode_useful_tokens),
            "decode_utilization": (
                self.decode_useful_tokens / self.decode_slot_steps
                if self.decode_slot_steps else 0.0),
            # prefix cache: hit tokens = prompt tokens whose KV was
            # spliced from cached blocks instead of re-prefilled;
            # hit rate is over all prompt tokens seen at admission
            "prefix_cache_hit_tokens": cache.prefix_hit_tokens,
            "prefix_cache_hit_rate": (
                cache.prefix_hit_tokens / cache.prefix_query_tokens
                if cache.prefix_query_tokens else 0.0),
            "prefix_cache_evictions": cache.prefix_evictions,
            "free_blocks": cache.free_blocks,
            "cached_blocks": cache.cached_blocks,
            # -- quantized KV cache (ISSUE 13) ------------------------
            # kv_quant: the pool's storage mode ("fp32"-family dtype
            # name or "int8"); kv_pool_bytes / kv_bytes_per_token: the
            # pool's logical device footprint (sidecar scales
            # included) — the capacity headline the int8 pool roughly
            # halves. Pool-geometry gauges: clear_finished leaves them
            # at the same recomputed values (pinned by the reset test)
            # while every counter around them drops to zero.
            "kv_quant": self.kv_quant or cache.pool_dtype,
            "kv_pool_bytes": cache.pool_bytes(),
            "kv_bytes_per_token": cache.bytes_per_token(),
            # -- program observatory (ISSUE 14) -----------------------
            # program_compiles: trace+lower+compile events the watch
            # observed (warmup's grid lands here); unexpected_
            # recompiles: compiles AFTER seal_programs() — the runtime
            # FC2xx, asserted zero by chaos legs and the bench;
            # profiled_dispatches: sampled-attribution fences taken;
            # draft_acceptance_ema: the per-window acceptance EMA the
            # acceptance_ema counter track samples (adaptive-window
            # signal for ROADMAP 2)
            "program_compiles": self.program_compiles,
            "unexpected_recompiles": self.unexpected_recompiles,
            "programs_sealed": self.compile_watch.sealed,
            "profiled_dispatches": self.profiled_dispatches,
            "draft_acceptance_ema": float(self.draft_acceptance_ema),
        }
        if self._slo is not None:
            # declared-SLO evaluation over the sliding windows: per
            # policy/metric burn rates + headroom (telemetry.
            # SLOMonitor.evaluate); the fleet Router rolls the
            # per-replica headrooms up for SLO-aware routing. The
            # nested dict rides stats() only; the scalar
            # slo_min_headroom mirrors into the registry like every
            # other float
            slo = self._slo.evaluate()
            out["slo"] = slo
            out["slo_min_headroom"] = float(slo["min_headroom"])
            if self.tracer is not None:
                for pname, pol in slo["policies"].items():
                    if pol["violating"] and \
                            pname not in self._slo_violating:
                        self.tracer.event(
                            "slo_violation", pid=self.replica_id,
                            policy=pname, headroom=pol["headroom"])
                self._slo_violating = {
                    pname for pname, pol in slo["policies"].items()
                    if pol["violating"]}
                flat = {}
                for pname, pol in slo["policies"].items():
                    flat[f"{pname}.headroom"] = float(pol["headroom"])
                    for metric, md in pol["metrics"].items():
                        for wname, wd in md["windows"].items():
                            if wd["burn_rate"] is not None:
                                flat[f"{pname}.{metric}."
                                     f"burn_{wname}"] = \
                                    float(wd["burn_rate"])
                prefix = ("slo" if self.replica_id == 0
                          else f"slo.r{self.replica_id}")
                self.tracer.metrics.publish(prefix, flat)
        if self.tracer is not None:
            # the unified metrics registry mirrors this dict (ints ->
            # counters, floats -> gauges), so the stats() view and the
            # registry agree bit-for-bit — the cross-subsystem rollup
            # tests pin the parity. In a fleet the tracer is SHARED:
            # each replica publishes under its own namespace ("engine"
            # for replica 0 / a single engine, "engine1"... beyond),
            # so one replica's counters never masquerade as another's;
            # fleet-wide totals live under "fleet.*" and the shared
            # engine.itl_s/ttft_s/latency_s histograms ACCUMULATE
            # across replicas (a fleet-wide distribution by design).
            prefix = ("engine" if self.replica_id == 0
                      else f"engine{self.replica_id}")
            self.tracer.metrics.publish(prefix, out)
        return out

    # -- shutdown (ISSUE 19) -------------------------------------------------
    def close(self):
        """Graceful shutdown: collect every in-flight device chunk so
        dispatched buffers retire deterministically (nothing is left
        referencing pool pages), then mark the engine closed.
        Idempotent — a second close is a no-op; step()/add_request
        after close are not supported. The fleet transports call this
        from Router.close(), and a worker process calls it on its way
        out of the command loop."""
        if getattr(self, "_closed", False):
            return
        self._closed = True
        try:
            while self._inflight:
                self._collect_oldest()
        except Exception:       # noqa: BLE001 — shutdown path: a torn
            # collection must not keep the process alive; drop the
            # remaining entries (their requests stay non-terminal)
            self._inflight.clear()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False
