"""Multi-chip tensor-parallel serving (ISSUE 8).

Layers under test:
- SpecLayout: strict mode raises on weight-tree keys missing from
  CANONICAL_SPECS, and the FULL extraction key vocabulary of both
  decoders (PagedLlamaDecoder._extract_weights, PagedGPTDecoder's
  TP-split _extract_gpt_weights) is covered — a silently-replicated
  unknown key is how spec drift (and implicit all-gathers) starts;
- the EQuARX-style int8_all_reduce against a plain fp32 psum
  (bounded quantization error, exact shape/dtype contract);
- the ENGINE's tp=N path: the whole ragged [T, W] serving step under
  fully-manual shard_map must be a pure placement change — greedy and
  deterministic-rich outputs TOKEN-IDENTICAL at tp=1 vs tp=2/4 with
  fp32 comms (chunked prefill, prefix-cache splices, EOS cuts,
  preemption-with-recompute, and the GPT twin included), and
  identical greedy tokens under int8-compressed comms;
- the communication contract, asserted directly on the traced step
  program: exactly one psum per attention/MLP block per layer per
  ministep plus one logits all_gather per ministep, zero collectives
  on the KV-append path (the committed comm_expectations.json pins the
  same facts for the 4s gate).

PADDLE_TPU_POOL_DEBUG=1 (set by the invariant gate) makes every engine
step assert the pool invariant on the sharded pool too.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import LlamaForCausalLM, llama_tiny

os.environ.setdefault("PADDLE_TPU_POOL_DEBUG", "1")


def _mesh(n, axis="tp"):
    import jax
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]), (axis,))


# ---------------------------------------------------------------------------
# SpecLayout: strict coverage (satellite: no silent replication)
# ---------------------------------------------------------------------------

def _tree_keys(weights):
    keys = set()
    for k, v in weights.items():
        if k == "layers":
            for layer in v:
                keys.update(layer)
        else:
            keys.add(k)
    return keys


class TestSpecLayoutStrict:
    def test_strict_raises_on_unknown_key(self):
        from paddle_tpu.distributed.spec_layout import SpecLayout
        lay = SpecLayout()
        with pytest.raises(KeyError, match="no canonical"):
            lay.spec("wot_is_this", strict=True)
        # non-strict keeps the replicate-unknowns contract
        assert tuple(lay.spec("wot_is_this")) == ()

    def test_strict_apply_raises_on_unknown_tree_key(self):
        import jax.numpy as jnp
        from paddle_tpu.distributed.spec_layout import SpecLayout
        w = {"embed": jnp.zeros((8, 4)),
             "layers": [{"wq": jnp.zeros((4, 4)),
                         "mystery": jnp.zeros((4,))}]}
        with pytest.raises(KeyError, match="mystery"):
            SpecLayout().apply(_mesh(2), w, strict=True)

    def test_llama_extraction_vocabulary_covered(self):
        """Every key _extract_weights can emit (fused keys excluded:
        fusion only happens on the single-device path, which never
        places) has a canonical spec — strict apply must never fire on
        a real Llama serving tree."""
        from paddle_tpu.distributed.spec_layout import CANONICAL_SPECS
        from paddle_tpu.inference.paged_decode import _extract_weights
        paddle.seed(0)
        model = LlamaForCausalLM(llama_tiny())
        model.eval()
        keys = _tree_keys(_extract_weights(model))
        missing = keys - set(CANONICAL_SPECS)
        assert not missing, f"uncovered Llama weight keys: {missing}"

    def test_gpt_tp_vocabulary_covered(self):
        """The GPT TP-split tree (what SpecLayout.apply actually
        places) is fully covered; the fused single-device keys
        (wqkv/bqkv) are intentionally NOT in the table — a naive
        column split of the fused out dim would mix q/k/v features."""
        from paddle_tpu.distributed.spec_layout import CANONICAL_SPECS
        from paddle_tpu.inference.gpt_decode import _extract_gpt_weights
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny
        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny())
        model.eval()
        keys = _tree_keys(_extract_gpt_weights(model, tp_split=True))
        missing = keys - set(CANONICAL_SPECS)
        assert not missing, f"uncovered GPT TP weight keys: {missing}"
        assert "wqkv" not in CANONICAL_SPECS
        assert "bqkv" not in CANONICAL_SPECS

    def test_quantized_pair_placement(self):
        """(w_q, scale) pairs place by the weight's spec; the scale
        follows the OUT dim (sharded for column-parallel, replicated
        for row-parallel)."""
        import jax.numpy as jnp
        from paddle_tpu.distributed.spec_layout import SpecLayout
        lay = SpecLayout()
        w = {"layers": [{
            "wq": (jnp.zeros((16, 16), jnp.int8), jnp.ones(16)),
            "wo": (jnp.zeros((16, 16), jnp.int8), jnp.ones(16))}]}
        placed = lay.apply(_mesh(2), w, strict=True)
        wq, wq_s = placed["layers"][0]["wq"]
        wo, wo_s = placed["layers"][0]["wo"]
        assert tuple(wq.sharding.spec) == (None, "tp")
        assert tuple(wq_s.sharding.spec) == ("tp",)
        assert tuple(wo.sharding.spec) == ("tp", None)
        assert tuple(wo_s.sharding.spec) == ()

    def test_cache_spec_matches_pool_layout(self):
        """The canonical pool spec shards dim 1 — the kv-head dim of
        the REAL [num_blocks, kv_heads, block_size, head_dim] layout
        (ops.paged_attention.PagedKVCache)."""
        from paddle_tpu.distributed.spec_layout import CANONICAL_SPECS
        assert tuple(CANONICAL_SPECS["cache_k"]) == \
            (None, "tp", None, None)
        assert tuple(CANONICAL_SPECS["cache_v"]) == \
            (None, "tp", None, None)


# ---------------------------------------------------------------------------
# int8 compressed allreduce vs fp32 psum
# ---------------------------------------------------------------------------

class TestInt8AllReduce:
    def _run(self, body, x, n):
        import jax
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        mesh = _mesh(n, "rank")
        f = shard_map(body, mesh=mesh, in_specs=(P("rank"),),
                      out_specs=P("rank"), check_vma=False)
        return np.asarray(jax.jit(f)(x))

    def test_matches_psum_within_quantization_error(self):
        import jax
        from paddle_tpu.distributed.collective import \
            int8_all_reduce_body
        rng = np.random.RandomState(0)
        n = 4
        x = rng.randn(n, 6, 64).astype(np.float32)
        got = self._run(int8_all_reduce_body(n), x, n)
        want = self._run(lambda a: jax.lax.psum(a, "rank"), x, n)
        # two absmax-symmetric int8 roundings: error bounded by ~2
        # quantization steps of the summed magnitude
        step = np.abs(x).max() / 127.0 * n + np.abs(want).max() / 127.0
        assert np.abs(got - want).max() <= 2.05 * step
        assert got.dtype == want.dtype and got.shape == want.shape

    def test_indivisible_dim_falls_back_to_psum_exactly(self):
        import jax
        from paddle_tpu.distributed.collective import \
            int8_all_reduce_body
        rng = np.random.RandomState(1)
        x = rng.randn(2, 3, 7).astype(np.float32)   # 7 % 2 != 0
        got = self._run(int8_all_reduce_body(2), x, 2)
        want = self._run(lambda a: jax.lax.psum(a, "rank"), x, 2)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# engine A/B: tp=1 vs tp=2/4, fp32 and int8 comms
# ---------------------------------------------------------------------------

def _mk_model(**cfg_kw):
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny(**cfg_kw))
    model.eval()
    return model


class TestTPEngineIdentity:
    def setup_method(self):
        self.model = _mk_model()
        self.rng = np.random.RandomState(17)

    def _prompt(self, n):
        return self.rng.randint(0, 512, n).astype(np.int32)

    def _run(self, model, reqs, **kw):
        from paddle_tpu.inference import ServingEngine
        kw.setdefault("max_batch_size", 3)
        kw.setdefault("num_blocks", 96)
        kw.setdefault("block_size", 8)
        kw.setdefault("prompt_buckets", (8, 16, 32, 64))
        kw.setdefault("chunk_size", 4)
        kw.setdefault("prefill_chunk", 8)
        kw.setdefault("ragged", True)
        eng = ServingEngine(model, **kw)
        rids = [eng.add_request(p, s) for p, s in reqs]
        eng.run_to_completion()
        return [eng.result(r).tolist() for r in rids], eng.stats()

    def test_greedy_identity_tp2_mixed_lengths_chunked(self):
        """Mixed prompt lengths incl. a multi-chunk prompt: the tp=2
        sharded step must be token-identical to tp=1."""
        from paddle_tpu.inference import SamplingParams
        reqs = [(self._prompt(n), SamplingParams(max_new_tokens=m))
                for n, m in ((5, 10), (30, 12), (60, 8), (9, 6))]
        base, _ = self._run(self.model, reqs)
        tp2, st = self._run(self.model, reqs, tp=2)
        assert tp2 == base
        # the sharded path is still one program per step
        assert st["device_dispatches"] > 0

    def test_greedy_identity_tp4(self):
        """tp=4 needs kv heads divisible by 4 — the kvh=4 twin config;
        identity holds across the deeper shard."""
        from paddle_tpu.inference import SamplingParams
        model = _mk_model(num_key_value_heads=4)
        reqs = [(self._prompt(n), SamplingParams(max_new_tokens=m))
                for n, m in ((7, 10), (18, 8), (29, 9))]
        base, _ = self._run(model, reqs)
        tp4, _ = self._run(model, reqs, tp=4)
        assert tp4 == base

    def test_greedy_identity_shared_prefix_splice(self):
        """Prefix-cache splices (incl. splice-pending readers on a
        still-prefilling writer) ride the kv-head-sharded pool: blocks
        written by shard-local appends splice identically."""
        from paddle_tpu.inference import SamplingParams
        base_p = self._prompt(16)
        reqs = [(np.concatenate([base_p, self._prompt(6)]),
                 SamplingParams(max_new_tokens=8)),
                (np.concatenate([base_p, self._prompt(9)]),
                 SamplingParams(max_new_tokens=8)),
                (self._prompt(11), SamplingParams(max_new_tokens=8))]
        base, st_b = self._run(self.model, reqs)
        tp2, st_t = self._run(self.model, reqs, tp=2)
        assert tp2 == base
        assert st_t["prefix_cache_hit_tokens"] == \
            st_b["prefix_cache_hit_tokens"] > 0

    def test_rich_sampling_identity_tp2(self):
        """Per-request top_k/top_p/repetition_penalty (the rich program
        twin) under sharding: the engine PRNG stream is host-side and
        the gathered logits replicated, so sampled streams match
        exactly."""
        from paddle_tpu.inference import SamplingParams
        reqs = [(self._prompt(n),
                 SamplingParams(max_new_tokens=8, temperature=0.8,
                                top_k=40, top_p=0.9,
                                repetition_penalty=1.2))
                for n in (6, 13, 21)]
        base, _ = self._run(self.model, reqs)
        tp2, _ = self._run(self.model, reqs, tp=2)
        assert tp2 == base

    def test_eos_cut_identity_tp2(self):
        from paddle_tpu.inference import SamplingParams
        p = self._prompt(10)
        stream, _ = self._run(self.model,
                              [(p, SamplingParams(max_new_tokens=12))])
        eos = stream[0][len(stream[0]) // 2]
        reqs = [(p, SamplingParams(max_new_tokens=12,
                                   eos_token_id=eos)),
                (self._prompt(7), SamplingParams(max_new_tokens=12))]
        base, _ = self._run(self.model, reqs)
        tp2, _ = self._run(self.model, reqs, tp=2)
        assert tp2 == base
        assert tp2[0][-1] == eos and len(tp2[0]) < 12

    def test_preemption_recompute_identity_tp2(self):
        """OOM-driven preemption-with-recompute on the SHARDED engine:
        row-range neutralization and no-sample re-prefill stay
        request-granular; outputs match an unpressured tp=1 run."""
        from paddle_tpu.inference import SamplingParams
        reqs = [(self._prompt(n), SamplingParams(max_new_tokens=24))
                for n in (8, 16, 24, 8, 12)]
        base, _ = self._run(self.model, reqs, num_blocks=96)
        out, st = self._run(self.model, reqs, tp=2, num_blocks=12,
                            admission="optimistic")
        assert st["preemptions"] >= 1
        assert out == base

    def test_int8_comm_logits_tolerance_and_greedy_identity(self):
        """The accuracy A/B of the EQuARX-style compressed allreduce
        (tp_comm="int8"): per-step logits stay within a small relative
        tolerance (2%) of the fp32-comm shard. The comparison crosses a
        quantisation, so the greedy streams are held to what that bound
        leaves: a near-tie whose gap sits below the quantization error
        can legitimately flip under compressed comms (the flag's
        contract, which is why the flag exists and fp32 is the
        default), and on these random weights one does: request 3 parts
        at its 5th token, where the exact logits differ by 5.3e-4 of a
        largest logit of 1.82. Up to such a position the streams are
        equal. The same arithmetic (tp=2, fp32 comms) is held to token
        identity."""
        import jax
        from jax.sharding import Mesh
        from near_tie import assert_same_until_near_tie
        from paddle_tpu.inference import SamplingParams
        from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
        # 1) the streams on the pinned workload
        reqs = [(self._prompt(n), SamplingParams(max_new_tokens=m))
                for n, m in ((5, 10), (12, 8), (30, 12), (9, 6),
                             (17, 10))]
        base, _ = self._run(self.model, reqs)
        fp32, _ = self._run(self.model, reqs, tp=2)
        assert fp32 == base
        int8, _ = self._run(self.model, reqs, tp=2, tp_comm="int8")
        same = [assert_same_until_near_tie(self.model, p, want, got,
                                           rel=0.02)
                for (p, _), want, got in zip(reqs, base, int8)]
        assert sum(same) >= len(reqs) - 1
        # 2) logits tolerance, measured shard-for-shard on one prefill
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
        ctx = reqs[2][0][None].astype(np.int32)

        def logits_of(tp_comm):
            d = PagedLlamaDecoder(self.model, num_blocks=64,
                                  block_size=8, mesh=mesh,
                                  mp_axis="tp", tp_shard_map=True,
                                  tp_comm=tp_comm)
            c = d.cache
            c.allocate(0, ctx.shape[1] + 1)
            slots = np.asarray(
                [[c.extend(0) for _ in range(ctx.shape[1])]], np.int32)
            lg, c.k, c.v = d._prefill(d.weights, c.k, c.v, ctx, slots)
            return np.asarray(lg)[0]

        lf, li = logits_of("fp32"), logits_of("int8")
        rel = np.abs(lf - li).max() / np.abs(lf).max()
        assert rel < 0.02, f"int8-comm logits off by {rel:.4f} rel"
        assert int(lf.argmax()) == int(li.argmax())

    def test_gpt_twin_identity(self):
        import jax
        from paddle_tpu.inference import ServingEngine, SamplingParams
        from paddle_tpu.inference.gpt_decode import PagedGPTDecoder
        from paddle_tpu.models import GPTForCausalLM, gpt_tiny
        paddle.seed(0)
        model = GPTForCausalLM(gpt_tiny())
        model.eval()
        prompts = [self._prompt(n) for n in (5, 14, 28)]
        outs = []
        for tp in (1, 2):
            if tp > 1:
                dec = PagedGPTDecoder(model, num_blocks=64,
                                      block_size=8, mesh=_mesh(tp),
                                      tp_shard_map=True)
            else:
                dec = PagedGPTDecoder(model, num_blocks=64,
                                      block_size=8)
            eng = ServingEngine(dec, max_batch_size=3,
                                prompt_buckets=(8, 16, 32),
                                chunk_size=4, prefill_chunk=8,
                                ragged=True, tp=tp)
            rids = [eng.add_request(p,
                                    SamplingParams(max_new_tokens=10))
                    for p in prompts]
            eng.run_to_completion()
            outs.append([eng.result(r).tolist() for r in rids])
        assert outs[0] == outs[1]

    def test_decoder_generate_identity_tp2(self):
        """The decoder's own generate() (batch API) runs fully-manual
        too — prefill + decode-scan wrapped at construction."""
        from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
        ids = self.rng.randint(0, 512, (2, 7)).astype(np.int32)
        d1 = PagedLlamaDecoder(self.model, num_blocks=64, block_size=8)
        o1 = d1.generate(ids, max_new_tokens=8)
        d2 = PagedLlamaDecoder(self.model, num_blocks=64, block_size=8,
                               mesh=_mesh(2), mp_axis="tp",
                               tp_shard_map=True)
        o2 = d2.generate(ids, max_new_tokens=8)
        assert o1.tolist() == o2.tolist()


# ---------------------------------------------------------------------------
# engine surface / error contract
# ---------------------------------------------------------------------------

class TestTPEngineSurface:
    def test_tp_forces_ragged(self):
        from paddle_tpu.inference import ServingEngine
        eng = ServingEngine(_mk_model(), max_batch_size=2,
                            num_blocks=32, block_size=8,
                            prompt_buckets=(16,), ragged=False, tp=2)
        assert eng.ragged and eng.tp == 2

    def test_tp_and_mesh_conflict(self):
        from jax.sharding import Mesh  # noqa: F401
        from paddle_tpu.inference import ServingEngine
        with pytest.raises(ValueError, match="not both"):
            ServingEngine(_mk_model(), tp=2, mesh=_mesh(2, "mp"))

    def test_prebuilt_decoder_tp_mismatch(self):
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
        dec = PagedLlamaDecoder(_mk_model(), num_blocks=32,
                                block_size=8, mesh=_mesh(2),
                                mp_axis="tp", tp_shard_map=True)
        with pytest.raises(ValueError, match="tp degree"):
            ServingEngine(dec, tp=4)
        # matching degree (or tp left at 1) infers from the decoder
        eng = ServingEngine(dec, tp=2, max_batch_size=2,
                            prompt_buckets=(16,))
        assert eng.tp == 2

    def test_prebuilt_decoder_tp_comm_mismatch(self):
        """A non-default tp_comm that contradicts the prebuilt
        decoder's baked-in comm mode must raise — silently adopting
        the decoder's would run an fp32-vs-fp32 'A/B' the caller
        believes is int8."""
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
        dec = PagedLlamaDecoder(_mk_model(), num_blocks=32,
                                block_size=8, mesh=_mesh(2),
                                mp_axis="tp", tp_shard_map=True)
        with pytest.raises(ValueError, match="tp_comm"):
            ServingEngine(dec, tp=2, tp_comm="int8")
        # the MIRROR direction too: an explicit fp32 against an int8
        # decoder must raise, not silently run the quantized leg
        dec8 = PagedLlamaDecoder(_mk_model(), num_blocks=32,
                                 block_size=8, mesh=_mesh(2),
                                 mp_axis="tp", tp_shard_map=True,
                                 tp_comm="int8")
        with pytest.raises(ValueError, match="tp_comm"):
            ServingEngine(dec8, tp=2, tp_comm="fp32")
        # tp_comm=None (default) adopts the decoder's mode
        eng = ServingEngine(dec8, tp=2, max_batch_size=2,
                            prompt_buckets=(16,))
        assert eng.tp_comm == "int8"

    def test_bad_tp_comm_rejected(self):
        from paddle_tpu.inference import ServingEngine
        with pytest.raises(ValueError, match="tp_comm"):
            ServingEngine(_mk_model(), tp=2, tp_comm="fp8")

    def test_tp_flags_without_mesh_fail_loudly(self):
        """tp_shard_map=True without a mesh (and tp_comm='int8' off
        the manual path) must raise, not silently build an unsharded
        decoder — at 8B scale the silent version OOMs a chip with no
        hint the TP request was dropped."""
        from paddle_tpu.inference import ServingEngine
        from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
        m = _mk_model()
        with pytest.raises(ValueError, match="needs a mesh"):
            PagedLlamaDecoder(m, num_blocks=32, block_size=8,
                              tp_shard_map=True)
        with pytest.raises(ValueError, match="int8"):
            PagedLlamaDecoder(m, num_blocks=32, block_size=8,
                              tp_comm="int8")
        with pytest.raises(ValueError, match="int8"):
            # engine at tp=1 with a compressed-comm request: the
            # decoder it builds rejects the dropped flag
            ServingEngine(m, tp_comm="int8", max_batch_size=2,
                          num_blocks=32, block_size=8,
                          prompt_buckets=(16,))

    def test_indivisible_heads_rejected(self):
        from paddle_tpu.inference import ServingEngine
        with pytest.raises(ValueError, match="divisible"):
            # llama_tiny has 2 kv heads: tp=4 cannot shard them
            ServingEngine(_mk_model(), tp=4)


# ---------------------------------------------------------------------------
# communication contract of the step program (traced, not profiled)
# ---------------------------------------------------------------------------

class TestStepProgramCommContract:
    def _rows(self, tp_comm):
        import jax
        from tools.flightcheck.comm_audit import (_build_tp_serving,
                                                  audit_jaxpr)
        build = _build_tp_serving()[f"serving.ragged_tp2_{tp_comm}"]
        fn, args = build()
        return audit_jaxpr(jax.make_jaxpr(fn)(*args))[0]

    def test_fp32_exactly_one_psum_per_block(self):
        """T=2 ministeps x 2 layers x 2 blocks = 8 psums, one logits
        all_gather per ministep, nothing else — in particular ZERO
        collectives on the KV-append path (reshape_and_cache into the
        kv-head-sharded pool is shard-local)."""
        rows = self._rows("fp32")
        by_kind = {}
        for r in rows:
            by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + r["count"]
        assert by_kind == {"psum": 8, "all_gather": 2}, rows

    def test_int8_blocks_use_quantized_collective(self):
        """Under tp_comm="int8" every block psum becomes the
        quantized collective (2 all_to_alls + 2 all_gathers); the
        logits gather stays (exact)."""
        rows = self._rows("int8")
        by_kind = {}
        for r in rows:
            by_kind[r["kind"]] = by_kind.get(r["kind"], 0) + r["count"]
        # 8 blocks x 2 all_to_alls (int8 chunks + per-row scales)
        assert by_kind["all_to_all"] == 16, rows
        assert "psum" not in by_kind, rows
        # 8 blocks x (chunk + scale) gathers + 2 logits gathers
        assert by_kind["all_gather"] == 18, rows
