"""Quantized KV cache (ISSUE 13): int8 pages in the paged pool with
dequant fused into the attention reads.

Layers under test:
- quantize/dequant ROUND TRIP: reshape_and_cache on an (int8, scales)
  pool must store every K/V row within half a quantization step of the
  original (per-row-per-kv-head absmax, step = absmax/127), and the
  sidecar scales must land at the written slots only;
- KERNEL vs ORACLE on the int8 pool: the Pallas ragged kernel's fused
  per-page-DMA dequant (interpret mode on CPU) against the jnp
  oracle's gather-time dequant — randomized geometries, context
  lengths exactly at page boundaries, grid-padding rows exactly zero;
- the ENGINE A/B accuracy contract: greedy outputs on the int8 pool
  TOKEN-IDENTICAL to the fp32 pool across the serving matrix —
  chunked prefill, prefix splices, preemption-recompute on tight
  pools, speculative-decode verify windows, LoRA tenants, tp=2, the
  GPT twin (quantization noise sits far below the pinned workloads'
  logit gaps; a sub-quantization-step near-tie may legitimately flip,
  which is the flag's contract — these seeds don't);
- rollback / debug_check on the quantized layout (the allocator is
  byte-agnostic; the pool invariant must hold through speculative
  rollbacks and eviction on (int8, scales) planes);
- the stats()/telemetry surface: kv_quant / kv_pool_bytes /
  kv_bytes_per_token plumbing + clear_finished behavior, kv_alloc
  events carrying the pool dtype;
- the tp contract: canonical cache_k_scale/cache_v_scale specs shard
  the kv-head dim with their values, and the committed comm-audit
  expectations pin serving.ragged_kv8_tp2 byte-identical to
  serving.ragged_tp2_fp32 (zero new collectives).

PADDLE_TPU_POOL_DEBUG=1 (set by the invariant gate) makes every engine
step here assert the pool invariant on the int8 planes too.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny

os.environ.setdefault("PADDLE_TPU_POOL_DEBUG", "1")


def _quant_pool(nb, kvh, bs, d):
    import jax.numpy as jnp
    return ((jnp.zeros((nb, kvh, bs, d), jnp.int8),
             jnp.zeros((nb, kvh, bs), jnp.float32)),
            (jnp.zeros((nb, kvh, bs, d), jnp.int8),
             jnp.zeros((nb, kvh, bs), jnp.float32)))


# ---------------------------------------------------------------------------
# quantize/dequant round trip
# ---------------------------------------------------------------------------

class TestRoundTrip:
    def test_append_roundtrip_error_bound(self):
        """Every appended row dequantizes within half a quantization
        step (absmax/127 per row per kv head) of the original."""
        import jax.numpy as jnp
        from paddle_tpu.ops.paged_attention import reshape_and_cache
        rng = np.random.RandomState(0)
        nb, kvh, bs, d = 8, 2, 8, 32
        kc, vc = _quant_pool(nb, kvh, bs, d)
        n = 3 * bs + 5                      # lands mid-page
        # mixed magnitudes: each row carries its own scale, so one hot
        # row must not degrade its neighbours
        k = rng.randn(n, kvh, d) * rng.choice([0.01, 1.0, 50.0],
                                              (n, 1, 1))
        v = rng.randn(n, kvh, d)
        slots = np.arange(n, dtype=np.int32)
        kc, vc = reshape_and_cache(jnp.asarray(k, jnp.float32),
                                   jnp.asarray(v, jnp.float32),
                                   kc, vc, jnp.asarray(slots))
        for orig, (vals, scales) in ((k, kc), (v, vc)):
            deq = (np.asarray(vals, np.float32)
                   * np.asarray(scales)[..., None])
            # pool layout is [block, kvh, slot_in_block, d]: re-index
            got = np.stack([deq[s // bs, :, s % bs] for s in slots])
            step = np.abs(orig).max(axis=-1, keepdims=True) / 127.0
            assert np.all(np.abs(got - orig) <= step * 0.5 + 1e-7)

    def test_unwritten_slots_stay_zero(self):
        """Unwritten slots dequantize to exact zeros — matching the
        dense pool's zero init bit-for-bit."""
        import jax.numpy as jnp
        from paddle_tpu.ops.paged_attention import reshape_and_cache
        kc, vc = _quant_pool(4, 1, 8, 16)
        k = jnp.ones((2, 1, 16), jnp.float32)
        kc, vc = reshape_and_cache(k, k, kc, vc,
                                   jnp.asarray([3, 9], jnp.int32))
        vals, scales = kc
        mask = np.ones((4, 1, 8), bool)
        mask[0, 0, 3] = mask[1, 0, 1] = False
        assert np.all(np.asarray(vals)[mask.nonzero()[0],
                                       mask.nonzero()[1],
                                       mask.nonzero()[2]] == 0)

    def test_zero_rows_quantize_exactly(self):
        """All-zero K/V (the spec-decode neutralization write) stores
        exact zeros with unit scales — the scratch-page contract."""
        import jax.numpy as jnp
        from paddle_tpu.ops.paged_attention import reshape_and_cache
        kc, vc = _quant_pool(2, 2, 4, 8)
        z = jnp.zeros((3, 2, 8), jnp.float32)
        kc, vc = reshape_and_cache(z, z, kc, vc,
                                   jnp.asarray([0, 1, 2], jnp.int32))
        assert np.all(np.asarray(kc[0]) == 0)
        assert np.all(np.asarray(vc[0]) == 0)


# ---------------------------------------------------------------------------
# kernel vs oracle on the int8 pool
# ---------------------------------------------------------------------------

def _rand_quant_case(rng, kvh, group, d, bs, nblocks, mp, n_seqs,
                     decode_rows, chunk_rows):
    """A randomized ragged batch over a QUANTIZED pool: fp32 K/V
    appended through reshape_and_cache (so values and sidecar scales
    are exactly what serving writes), mixed decode/chunk/padding rows
    — the test_ragged_batching generator's int8 twin."""
    import jax.numpy as jnp
    from paddle_tpu.ops.paged_attention import reshape_and_cache
    kc, vc = _quant_pool(nblocks, kvh, bs, d)
    k = jnp.asarray(rng.randn(nblocks * bs, kvh, d), jnp.float32)
    v = jnp.asarray(rng.randn(nblocks * bs, kvh, d), jnp.float32)
    kc, vc = reshape_and_cache(
        k, v, kc, vc, jnp.arange(nblocks * bs, dtype=jnp.int32))
    tables = jnp.asarray(
        rng.choice(nblocks, (n_seqs, mp), replace=False).astype(np.int32))
    row_seq, row_ctx = [], []
    for i in range(decode_rows):
        row_seq.append(i % n_seqs)
        row_ctx.append(int(rng.randint(1, mp * bs + 1)))
    off = int(rng.randint(0, mp * bs - chunk_rows))
    s = n_seqs - 1
    for j in range(chunk_rows):
        row_seq.append(s)
        row_ctx.append(off + j + 1)
    row_seq += [0, 0]
    row_ctx += [0, 0]
    q = jnp.asarray(rng.randn(len(row_seq), kvh * group, d), jnp.float32)
    return (q, kc, vc, tables, jnp.asarray(row_seq, jnp.int32),
            jnp.asarray(row_ctx, jnp.int32))


class TestKernelVsOracleInt8:
    def test_property_randomized(self):
        from paddle_tpu.ops.paged_attention import \
            ragged_paged_attention_reference
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rng = np.random.RandomState(0)
        geoms = [
            dict(kvh=2, group=4, d=64, bs=16, nblocks=16, mp=4,
                 n_seqs=3, decode_rows=3, chunk_rows=7),
            dict(kvh=1, group=1, d=64, bs=8, nblocks=24, mp=5,
                 n_seqs=4, decode_rows=5, chunk_rows=4),
            dict(kvh=4, group=1, d=64, bs=8, nblocks=10, mp=3,
                 n_seqs=2, decode_rows=2, chunk_rows=11),
        ]
        for g in geoms:
            case = _rand_quant_case(rng, **g)
            ref = ragged_paged_attention_reference(*case)
            out = ragged_paged_attention_pallas(*case)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(ref),
                atol=2e-5, rtol=2e-4, err_msg=f"geom={g}")

    def test_page_boundary_masking(self):
        """Context lengths exactly at / around page boundaries mask
        identically — the sidecar scales must never leak a masked
        slot's contribution."""
        import jax.numpy as jnp
        from paddle_tpu.ops.paged_attention import \
            ragged_paged_attention_reference
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rng = np.random.RandomState(3)
        case = _rand_quant_case(rng, kvh=2, group=2, d=64, bs=8,
                                nblocks=8, mp=4, n_seqs=1,
                                decode_rows=0, chunk_rows=1)
        q, kc, vc, tables, _, _ = case
        bs, mp = 8, 4
        ctxs = [1, bs - 1, bs, bs + 1, 2 * bs, 3 * bs + 1, mp * bs]
        q = jnp.asarray(rng.randn(len(ctxs), 4, 64), jnp.float32)
        rs = jnp.zeros(len(ctxs), jnp.int32)
        rc = jnp.asarray(ctxs, jnp.int32)
        ref = ragged_paged_attention_reference(q, kc, vc, tables, rs, rc)
        out = ragged_paged_attention_pallas(q, kc, vc, tables, rs, rc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)

    def test_padding_rows_come_out_zero(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.paged_attention import \
            ragged_paged_attention_reference
        from paddle_tpu.ops.pallas.ragged_paged_attention import \
            ragged_paged_attention_pallas
        rng = np.random.RandomState(2)
        case = _rand_quant_case(rng, kvh=1, group=1, d=64, bs=8,
                                nblocks=4, mp=2, n_seqs=1,
                                decode_rows=1, chunk_rows=1)
        q, kc, vc, tables, rs, rc = case
        rc = jnp.asarray([5, 3, 0, 0], jnp.int32)
        ref = ragged_paged_attention_reference(q, kc, vc, tables, rs, rc)
        out = ragged_paged_attention_pallas(q, kc, vc, tables, rs, rc)
        assert np.all(np.asarray(ref[2:]) == 0)
        assert np.all(np.asarray(out[2:]) == 0)
        assert np.any(np.asarray(ref[0]) != 0)

    def test_decode_reference_dequantizes(self):
        """The dense decode oracle reads the same int8 pool the ragged
        oracle does — a pure decode-row batch matches row-for-row."""
        import jax.numpy as jnp
        from paddle_tpu.ops.paged_attention import (
            paged_attention_decode_reference,
            ragged_paged_attention_reference)
        rng = np.random.RandomState(1)
        case = _rand_quant_case(rng, kvh=2, group=4, d=64, bs=16,
                                nblocks=12, mp=3, n_seqs=3,
                                decode_rows=3, chunk_rows=1)
        q, kc, vc, tables, _, _ = case
        b = 3
        ctx = jnp.asarray([5, 37, 48], jnp.int32)
        qd = jnp.asarray(rng.randn(b, 8, 64), jnp.float32)
        dref = paged_attention_decode_reference(qd, kc, vc, tables, ctx)
        rref = ragged_paged_attention_reference(
            qd, kc, vc, tables, jnp.arange(b, dtype=jnp.int32), ctx)
        np.testing.assert_allclose(np.asarray(rref), np.asarray(dref),
                                   atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# engine A/B: int8 pool vs fp32 pool, greedy token identity
# ---------------------------------------------------------------------------

def _model():
    paddle.seed(0)
    cfg = llama_tiny()
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def _drain(eng, prompts, new=12, **kw):
    from paddle_tpu.inference import SamplingParams
    rids = [eng.add_request(p, SamplingParams(max_new_tokens=new, **kw))
            for p in prompts]
    eng.run_to_completion()
    return [eng.result(r).tolist() for r in rids]


def _prompts(cfg, lens=(9, 17, 30), seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


class TestEngineAccuracy:
    def _ab(self, mk_eng, run):
        outs = {}
        for kvq in (None, "int8"):
            outs[kvq] = run(mk_eng(kvq))
        assert outs["int8"] == outs[None], \
            "int8 KV pool changed greedy outputs"
        return outs[None]

    def test_ragged_identity_mixed_lengths(self):
        from paddle_tpu.inference import ServingEngine
        model, cfg = _model()
        self._ab(
            lambda kvq: ServingEngine(
                model, max_batch_size=3, num_blocks=32, block_size=8,
                prompt_buckets=(16, 32), chunk_size=4, prefill_chunk=8,
                ragged=True, kv_quant=kvq),
            lambda eng: _drain(eng, _prompts(cfg)))

    def test_dense_identity(self):
        """The dense per-phase scheduler serves the int8 pool too
        (its decode attention runs the dequantizing reference)."""
        from paddle_tpu.inference import ServingEngine
        model, cfg = _model()
        self._ab(
            lambda kvq: ServingEngine(
                model, max_batch_size=3, num_blocks=32, block_size=8,
                prompt_buckets=(16, 32), chunk_size=4, prefill_chunk=8,
                ragged=False, kv_quant=kvq),
            lambda eng: _drain(eng, _prompts(cfg)))

    def test_chunked_prefill_long_prompt(self):
        """A prompt spanning several prefill chunks: every later chunk
        re-reads earlier chunks' pages (quantized) as its prefix."""
        from paddle_tpu.inference import ServingEngine
        model, cfg = _model()
        self._ab(
            lambda kvq: ServingEngine(
                model, max_batch_size=2, num_blocks=48, block_size=8,
                prompt_buckets=(16, 128), chunk_size=4,
                prefill_chunk=16, ragged=True, kv_quant=kvq),
            lambda eng: _drain(eng, _prompts(cfg, lens=(100, 11))))

    def test_prefix_splice_identity(self):
        """Prefix-cache hits splice QUANTIZED blocks: the reader's
        suffix prefill attends dequantized prefix pages."""
        from paddle_tpu.inference import ServingEngine
        model, cfg = _model()
        rng = np.random.RandomState(5)
        shared = rng.randint(0, cfg.vocab_size, 24).astype(np.int32)
        tails = [rng.randint(0, cfg.vocab_size, 7).astype(np.int32)
                 for _ in range(3)]
        prompts = [np.concatenate([shared, t]) for t in tails]

        def run(eng):
            out = _drain(eng, prompts, new=8)
            assert eng.stats()["prefix_cache_hit_tokens"] > 0
            return out

        self._ab(
            lambda kvq: ServingEngine(
                model, max_batch_size=3, num_blocks=40, block_size=8,
                prompt_buckets=(32, 64), chunk_size=4, prefill_chunk=8,
                ragged=True, kv_quant=kvq),
            run)

    def test_preemption_recompute_tight_pool(self):
        """Optimistic admission on a tight int8 pool: preemption frees
        quantized blocks, the resume re-prefills through the no-sample
        chunks, debug_check holds after every step (POOL_DEBUG)."""
        from paddle_tpu.inference import ServingEngine
        model, cfg = _model()

        from near_tie import assert_same_until_near_tie
        prompts = _prompts(cfg)

        def run(kvq, num_blocks):
            eng = ServingEngine(
                model, max_batch_size=3, num_blocks=num_blocks,
                block_size=8, prompt_buckets=(16, 32), chunk_size=4,
                prefill_chunk=8, admission="optimistic", kv_quant=kvq)
            return _drain(eng, prompts, new=24), eng.stats()["preemptions"]

        tight, preempted = run("int8", 14)
        roomy, unpressed = run("int8", 48)
        assert preempted >= 1 and unpressed == 0
        # one engine against itself: recompute reads the same quantized
        # pages the first pass wrote, so preemption changes no token
        assert tight == roomy
        # against the fp32 pool the comparison crosses the quantisation:
        # K and V are rounded to 8 bits a row, the logits move by a
        # fraction of a percent, and on these random weights request 0
        # parts at its 17th token, where the exact logits differ by
        # 2.1e-3 of a largest logit of 1.82; 2% is the bound the int8
        # collectives' logits are held to in test_tp_serving.py
        dense, dense_preempted = run(None, 14)
        assert dense_preempted >= 1
        for p, want, got in zip(prompts, dense, tight):
            assert_same_until_near_tie(model, p, want, got, rel=0.02)

    def test_spec_decode_windows(self):
        """Verify windows ride the int8 pool: draft rows write
        quantized K/V, rejected tails neutralize + roll back, and
        greedy outputs still match the fp32-pool spec engine."""
        from paddle_tpu.inference import ServingEngine, SpecConfig

        model, cfg = _model()
        # REPETITIVE prompts (tiled 4-grams): the prompt-lookup
        # drafter needs a trailing n-gram that re-occurs earlier, or
        # no window ever rides the verify program
        rng = np.random.RandomState(11)
        prompts = [np.tile(rng.randint(0, cfg.vocab_size, 4)
                           .astype(np.int32), 6) for _ in range(3)]

        def run(eng):
            out = _drain(eng, prompts, new=16)
            assert eng.stats()["drafted_tokens"] > 0
            return out

        self._ab(
            lambda kvq: ServingEngine(
                model, max_batch_size=3, num_blocks=32, block_size=8,
                prompt_buckets=(16, 32), chunk_size=4, prefill_chunk=8,
                spec_decode=SpecConfig(draft_len=3), kv_quant=kvq),
            run)

    def test_lora_tenants(self):
        """Adapter deltas compose with the quantized pool (adapter
        pages stay f32 in the lora plane; only K/V quantizes)."""
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.lora import AdapterRegistry
        model, cfg = _model()

        def mk(kvq):
            reg = AdapterRegistry(rank=2)
            reg.register_random("t0", seed=5, scale=0.1)
            return ServingEngine(
                model, max_batch_size=3, num_blocks=40, block_size=8,
                prompt_buckets=(16, 32), chunk_size=4, prefill_chunk=8,
                lora=reg, kv_quant=kvq)

        self._ab(mk, lambda eng: _drain(eng, _prompts(cfg),
                                        adapter_id="t0"))

    def test_tp2_identity(self):
        """tp=2 on the kv-head-sharded int8 pool: each shard
        quantizes/dequantizes its own heads + scales; greedy outputs
        match the fp32-pool tp=2 engine."""
        import jax
        from jax.sharding import Mesh
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
        if len(jax.devices()) < 2:
            pytest.skip("needs >= 2 devices")
        model, cfg = _model()

        def mk(kvq):
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
            dec = PagedLlamaDecoder(model, num_blocks=32, block_size=8,
                                    mesh=mesh, mp_axis="tp",
                                    tp_shard_map=True, kv_quant=kvq)
            return ServingEngine(dec, tp=2, max_batch_size=3,
                                 prompt_buckets=(16, 32), chunk_size=4,
                                 prefill_chunk=8)

        self._ab(mk, lambda eng: _drain(eng, _prompts(cfg)))

    def test_gpt_twin(self):
        from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.gpt_decode import PagedGPTDecoder
        paddle.seed(0)
        gm = GPTForCausalLM(gpt_tiny())
        gm.eval()

        def mk(kvq):
            dec = PagedGPTDecoder(gm, num_blocks=32, block_size=8,
                                  kv_quant=kvq)
            return ServingEngine(dec, max_batch_size=3,
                                 prompt_buckets=(16, 32), chunk_size=4,
                                 prefill_chunk=8, ragged=True)

        self._ab(mk, lambda eng: _drain(eng, _prompts(gm.cfg,
                                                      lens=(9, 17))))


# ---------------------------------------------------------------------------
# allocator invariants on the quantized layout
# ---------------------------------------------------------------------------

class TestQuantizedPoolInvariants:
    def test_rollback_and_debug_check(self):
        """The allocator is byte-agnostic: rollback rescinds
        speculative slots and debug_check holds on (int8, scales)
        planes exactly as on dense ones."""
        from paddle_tpu.ops.paged_attention import PagedKVCache
        c = PagedKVCache(2, 8, 4, 2, 16, kv_quant="int8")
        c.allocate(0, 8)
        for _ in range(7):
            c.extend(0)
        pre_blocks = len(c.seq_blocks(0))
        for _ in range(4):          # speculative window past the table
            c.extend(0)
        c.debug_check()
        c.rollback(0, 7, min_blocks=pre_blocks)
        c.debug_check()
        assert c.context_len(0) == 7
        c.free(0)
        c.debug_check()

    def test_cache_rejects_unknown_mode(self):
        from paddle_tpu.ops.paged_attention import PagedKVCache
        with pytest.raises(ValueError, match="kv_quant"):
            PagedKVCache(1, 4, 4, 1, 8, kv_quant="fp8")

    def test_engine_prebuilt_mismatch_raises(self):
        """An explicit engine kv_quant contradicting a prebuilt
        decoder's pool raises (the tp_comm contract, applied to the
        pool layout)."""
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
        model, _ = _model()
        dec = PagedLlamaDecoder(model, num_blocks=16, block_size=8)
        with pytest.raises(ValueError, match="kv_quant"):
            ServingEngine(dec, max_batch_size=2,
                          prompt_buckets=(16, 32), kv_quant="int8")


# ---------------------------------------------------------------------------
# stats / telemetry / tp-layout surface
# ---------------------------------------------------------------------------

class TestStatsAndLayout:
    def test_stats_plumbing_and_reset(self):
        from paddle_tpu.inference import ServingEngine
        model, cfg = _model()
        eng = ServingEngine(model, max_batch_size=2, num_blocks=16,
                            block_size=8, prompt_buckets=(16, 32),
                            chunk_size=4, ragged=True,
                            kv_quant="int8")
        _drain(eng, _prompts(cfg, lens=(9,)), new=4)
        st = eng.stats()
        assert st["kv_quant"] == "int8"
        cache = eng.dec.cache
        # 2 layers x (k + v) x (int8 values + f32 scales)
        want = 2 * 2 * (16 * 2 * 8 * 32 + 16 * 2 * 8 * 4)
        assert st["kv_pool_bytes"] == want == cache.pool_bytes()
        assert st["kv_bytes_per_token"] == \
            pytest.approx(want / (16 * 8))
        # pool-geometry gauges survive clear_finished (recomputed from
        # the pool, not counters); the counters around them reset
        eng.clear_finished()
        st2 = eng.stats()
        assert st2["finished"] == 0 and st2["generated_tokens"] == 0
        assert st2["kv_quant"] == "int8"
        assert st2["kv_pool_bytes"] == want
        assert st2["kv_bytes_per_token"] == st["kv_bytes_per_token"]

    def test_fp32_engine_reports_pool_dtype(self):
        from paddle_tpu.inference import ServingEngine
        model, _ = _model()
        eng = ServingEngine(model, max_batch_size=2, num_blocks=16,
                            block_size=8, prompt_buckets=(16, 32))
        st = eng.stats()
        assert st["kv_quant"] == "float32"
        assert st["kv_bytes_per_token"] == \
            pytest.approx(st["kv_pool_bytes"] / (16 * 8))

    def test_bytes_per_token_reduction(self):
        """The headline: int8 pool >= 1.8x fewer KV bytes/token than
        the bf16 pool at head_dim 64+ (3.5x vs f32 at head_dim 32)."""
        from paddle_tpu.ops.paged_attention import PagedKVCache
        import jax.numpy as jnp
        fp = PagedKVCache(2, 8, 8, 2, 64, dtype=jnp.bfloat16)
        q8 = PagedKVCache(2, 8, 8, 2, 64, kv_quant="int8")
        assert fp.bytes_per_token() / q8.bytes_per_token() >= 1.8

    def test_kv_alloc_events_carry_pool_dtype(self):
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.utils.telemetry import Tracer
        model, cfg = _model()
        tracer = Tracer()
        eng = ServingEngine(model, max_batch_size=2, num_blocks=16,
                            block_size=8, prompt_buckets=(16, 32),
                            chunk_size=4, ragged=True, kv_quant="int8",
                            tracer=tracer)
        _drain(eng, _prompts(cfg, lens=(9,)), new=4)
        allocs = [r for r in tracer.records()
                  if r.get("name") == "kv_alloc"]
        assert allocs and all(
            r.get("args", {}).get("dtype") == "int8" for r in allocs)

    def test_scale_specs_shard_with_their_heads(self):
        """Canonical sidecar-scale specs: kv-head dim (dim 1) sharded
        exactly like the values' — dim-aligned, zero collectives."""
        from paddle_tpu.distributed.spec_layout import CANONICAL_SPECS
        assert tuple(CANONICAL_SPECS["cache_k_scale"]) == \
            (None, "tp", None)
        assert tuple(CANONICAL_SPECS["cache_v_scale"]) == \
            (None, "tp", None)
        assert CANONICAL_SPECS["cache_k"][1] == \
            CANONICAL_SPECS["cache_k_scale"][1]

    def test_comm_expectations_pin_zero_new_collectives(self):
        """The committed comm-audit expectations must carry the kv8
        serving entry BYTE-IDENTICAL to the fp32-pool entry — the
        quantized pool adds zero collectives under tp (the 4s-gate
        pin, checked here without tracing)."""
        from tools.flightcheck import comm_audit
        exp = comm_audit.load()
        assert "serving.ragged_kv8_tp2" in exp
        assert exp["serving.ragged_kv8_tp2"]["collectives"] == \
            exp["serving.ragged_tp2_fp32"]["collectives"]
