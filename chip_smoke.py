#!/usr/bin/env python3
"""Smallest proof that paddle_tpu's main paths start on the chip.

    python chip_smoke.py             # one TPU chip: serve phase, train phase
    python chip_smoke.py --chips 4   # four chips: tp=4 serving vs one device
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # rehearsal: ends "ok": false

One process, the entry points a user calls, random weights from --seed:

- serve: ``ServingEngine(PagedLlamaDecoder.from_config(llama_3_8b, int8
  weights), ragged=True)`` at the model's full width and depth with a
  4 GiB bf16 KV pool answers 8 greedy requests of mixed prompt length;
  every request must finish with the asked number of in-vocabulary
  tokens, the ragged step program must hold the Pallas paged-attention
  kernel (``tpu_custom_call``), and the kernel path's logits must agree
  with the jnp reference path on the pool the run left behind.
- train: ``paddle_tpu.jit.TrainStep`` on ``LlamaForCausalLM(llama_mid)``
  (bf16, AdamW, batch 4 x seq 2048) takes 5 steps on one fixed batch;
  every loss finite, the last below the first, the step program holding
  the flash-attention forward and backward kernels.
- ``--chips 4`` runs neither: the same 8 requests go once through a
  one-device engine and once through a tp=4 mesh engine (Llama-3-8B
  width, bf16 weights, 8 layers) and must give the same tokens, with no
  device holding more than twice its quarter of weights plus pool.

Each phase prints one JSON object per line; the last line of output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Anything else — no TPU, a phase that raises, a reference or interpret
path where a kernel belongs — ends with ``"ok": false`` and exit code 1.
"""
import argparse
import gc
import json
import os
import sys
import time
import traceback

# one compile cache: the caller's if JAX_COMPILATION_CACHE_DIR is set,
# else <checkout>/.jax_cache (its path is part of the cache key, so it
# must not move between runs of one checkout)
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))

N_REQUESTS = 8
MAX_BATCH = 4       # two waves of four: queueing and slot turnover run too
MAX_NEW = 32


class SmokeFailure(AssertionError):
    pass


def emit(**kw):
    print(json.dumps(kw), flush=True)


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def device_info():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def check_device(dev, chips):
    """The only place that decides whether this machine may pass."""
    check(dev["platform"] == "tpu",
          f"no TPU: jax.devices()[0].platform is {dev['platform']!r}")
    check(dev["count"] >= chips,
          f"--chips {chips} needs {chips} devices, found {dev['count']}")


def peak_bytes():
    import jax
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def release_device_memory():
    """Drop what the last phase left on the devices — compiled programs
    close over their decoder, so the caches go first — and fail here,
    by name, if more than a GiB of arrays survives."""
    import jax
    jax.clear_caches()
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    check(live <= 1 << 30, f"{live} bytes of arrays outlive their phase")
    return live


def shape_of(tree):
    import jax
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding)
        if isinstance(x, jax.Array) else x, tree)


def count_kernels(jitted, args, on_tpu, want, compiled=True):
    """tpu_custom_call count of ``jitted`` lowered (and, with
    ``compiled``, compiled) at ``args``' shapes. On the chip fewer than
    ``want`` is a failure: the program took an interpret or reference
    path."""
    lowered = jitted.lower(*args)
    out = {"lowered": lowered.as_text().count("tpu_custom_call")}
    if on_tpu and compiled:
        out["compiled"] = lowered.compile().as_text().count(
            "tpu_custom_call")
    check(not on_tpu or min(out.values()) >= want,
          f"expected >= {want} tpu_custom_call, found {out}: a kernel "
          f"fell back to interpret/reference")
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def serve_config(tiny, layers=None):
    from paddle_tpu.models import LlamaConfig, llama_3_8b
    if tiny:
        # same head geometry class (GQA, head_dim 128, kv heads a
        # multiple of 4 for the tp phase), toy widths
        cfg = LlamaConfig(vocab_size=512, hidden_size=1024,
                          intermediate_size=512, num_hidden_layers=2,
                          num_attention_heads=8, num_key_value_heads=4,
                          max_position_embeddings=512, dtype="bfloat16")
        geom = dict(block_size=16, num_blocks=96, bucket=256,
                    prompt_lo=10, prompt_hi=150)
    else:
        cfg = llama_3_8b(dtype="bfloat16",
                         **({"num_hidden_layers": layers} if layers else {}))
        # 128 KiB of bf16 K/V per token at 32 layers: 1024 pages of 32
        # tokens = 32k tokens = 4 GiB of pool
        geom = dict(block_size=32, num_blocks=1024, bucket=2048,
                    prompt_lo=100, prompt_hi=1500)
    return cfg, geom


def make_prompts(seed, vocab, lo, hi):
    import numpy as np
    rng = np.random.RandomState(seed)
    lens = np.linspace(lo, hi, N_REQUESTS).astype(int)
    rng.shuffle(lens)
    return [rng.randint(0, vocab, int(n)).astype(np.int32) for n in lens]


def build_engine(cfg, geom, seed, weight_dtype, mesh=None):
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
    kw = dict(mesh=mesh, mp_axis="tp", tp_shard_map=True) if mesh else {}
    dec = PagedLlamaDecoder.from_config(
        cfg, seed=seed, weight_dtype=weight_dtype,
        block_size=geom["block_size"], num_blocks=geom["num_blocks"],
        **kw)
    return ServingEngine(dec, max_batch_size=MAX_BATCH,
                         prompt_buckets=(geom["bucket"],), ragged=True,
                         seed=seed)


def serve_requests(eng, prompts, vocab):
    """Submit, drain, and hold every request to the contract. Returns
    {request index: generated tokens}."""
    import numpy as np
    from paddle_tpu.inference import SamplingParams
    rids = [eng.add_request(p, SamplingParams(max_new_tokens=MAX_NEW))
            for p in prompts]
    eng.run_to_completion()
    out = {}
    for i, rid in enumerate(rids):
        req = eng.request(rid)
        check(req.state == "done",
              f"request {i} ended {req.state!r}: {req.error}")
        toks = np.asarray(eng.result(rid))
        check(toks.shape == (MAX_NEW,),
              f"request {i}: {toks.shape} tokens, asked {MAX_NEW}")
        check(bool(((toks >= 0) & (toks < vocab)).all()),
              f"request {i}: token outside the vocabulary")
        out[i] = toks
    return out


def record_ragged_dispatches(eng):
    """Keep the operand shapes of every ragged step program the engine
    dispatches (its pools are donated, so shapes, not arrays)."""
    seen = {}
    inner = eng._device_call

    def recording(kind, fn, *args):
        if kind == "dispatch:ragged":
            sds = shape_of(args)
            seen[tuple(sds[3].shape)] = (fn, sds)     # [T, W] -> call
        return inner(kind, fn, *args)

    eng._device_call = recording
    return seen


def logits_vs_reference(eng, geom, layers=2):
    """One decode step's logits through the decoder's own ragged step,
    kernel path against reference path, over the first ``layers``
    layers at full width on the pool the run left behind."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.utils.flags import FLAGS
    dec, cache = eng.dec, eng.dec.cache
    layers = min(layers, len(cache.k))
    w = dict(dec.weights, layers=dec.weights["layers"][:layers])
    bs, rows = geom["block_size"], N_REQUESTS
    per_seq = min(dec.max_pages, (cache.num_blocks - 1) // rows)
    tables = np.full((rows + 1, dec.max_pages), eng._scratch_block,
                     np.int32)
    for r in range(rows):
        tables[r, :per_seq] = 1 + r * per_seq + np.arange(per_seq)
    ctx = np.linspace(bs + 1, per_seq * bs, rows).astype(np.int32)
    rng = np.random.RandomState(0)
    ops = (jnp.asarray(rng.randint(0, dec.cfg.vocab_size, rows), jnp.int32),
           jnp.asarray(ctx), jnp.full((rows,), eng._scratch_slot, jnp.int32),
           jnp.arange(rows, dtype=jnp.int32), jnp.asarray(ctx),
           jnp.asarray(tables))

    def one_step():
        # a fresh jit per flag value: the flag is read while tracing
        f = jax.jit(lambda w_, k, v, *a: dec._ragged_logits(w_, k, v, *a),
                    donate_argnums=(1, 2))
        k, v = cache.k[:layers], cache.v[:layers]
        logits, k, v = f(w, k, v, *ops)
        cache.k[:layers], cache.v[:layers] = k, v
        return np.asarray(logits, np.float32)

    got = one_step()
    FLAGS.use_pallas_kernels = False
    try:
        ref = one_step()
    finally:
        FLAGS.use_pallas_kernels = True
    err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
    check(np.isfinite(got).all() and np.isfinite(ref).all(),
          "non-finite logits")
    # the interpret-mode kernel tests hold 2e-5 in f32; on the chip the
    # pool and the activations are bf16 on both sides, so this holds
    # what tests/test_tp_serving.py holds logits to: 2% of the largest
    check(err <= 0.02 * scale,
          f"kernel logits differ from reference: {err} vs scale {scale}")
    return {"layers": layers, "rows": rows, "max_abs_err": err,
            "max_abs_logit": scale}


def phase_serve(args, dev):
    on_tpu = dev["platform"] == "tpu"
    cfg, geom = serve_config(args.tiny)
    t0 = time.perf_counter()
    eng = build_engine(cfg, geom, args.seed, "int8")
    t_build = time.perf_counter() - t0
    impl = eng.attention_impls["ragged"]
    check(impl == "pallas" or not on_tpu,
          f"ragged step would serve through {impl}")
    t0 = time.perf_counter()
    eng.warmup(prompt_len=geom["prompt_hi"])
    t_warm = time.perf_counter() - t0
    warm_compiles = eng.compile_watch.compiles
    seen = record_ragged_dispatches(eng)

    prompts = make_prompts(args.seed, cfg.vocab_size, geom["prompt_lo"],
                           geom["prompt_hi"])
    t0 = time.perf_counter()
    toks = serve_requests(eng, prompts, cfg.vocab_size)
    t_run = time.perf_counter() - t0
    st = eng.stats()
    # every program the engine compiled: family, [T x W], seconds
    programs = [[r["family"], r["signature"].split("]")[0].split("[")[1],
                 round(r["wall_s"], 2)] for r in eng.compile_watch.records]
    emit(phase="serve", model="llama_3_8b" if not args.tiny else "tiny",
         layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
         vocab=cfg.vocab_size, weight_dtype="int8",
         kv_pool_bytes=st["kv_pool_bytes"],
         kv_pool_tokens=geom["num_blocks"] * geom["block_size"],
         prompt_tokens=[int(len(p)) for p in prompts],
         requests_done=len(toks), tokens_generated=st["generated_tokens"],
         attention=eng.attention_impls,
         setup_s={"build": round(t_build, 2), "warmup": round(t_warm, 2)},
         run_s=round(t_run, 2),
         compiles={"warmup": warm_compiles,
                   "run": eng.compile_watch.compiles - warm_compiles},
         programs=programs, peak_bytes_in_use=peak_bytes())

    # the step programs the run dispatched: kernel present, one per layer
    t0 = time.perf_counter()
    kernels = {}
    for (t, w), (fn, sds) in sorted(seen.items()):
        kernels[f"ragged[T={t},W={w}]"] = count_kernels(
            fn, sds, on_tpu, want=cfg.num_hidden_layers)
    check(kernels, "no ragged step program was dispatched")
    numerics = logits_vs_reference(eng, geom)
    emit(phase="serve_checks", tpu_custom_calls=kernels,
         logits_vs_reference=numerics,
         check_s=round(time.perf_counter() - t0, 2))
    eng.close()


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def phase_train(args, dev):
    import jax
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.models import LlamaForCausalLM, llama_mid, llama_tiny
    on_tpu = dev["platform"] == "tpu"
    paddle.seed(args.seed)
    if args.tiny:
        cfg, batch, seq = llama_tiny(dtype="bfloat16"), 2, 64
    else:
        cfg, batch, seq = llama_mid(dtype="bfloat16",
                                    use_recompute=False), 4, 2048
    t0 = time.perf_counter()
    model = LlamaForCausalLM(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(), weight_decay=0.01)
    step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
    rng = np.random.RandomState(args.seed)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32))
    losses = [float(step(ids, ids))]          # compiles
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    losses += [float(step(ids, ids)) for _ in range(4)]
    t_run = time.perf_counter() - t0
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")

    # forward, dq and dk/dv flash kernels of every layer in the step
    sds = shape_of(([p._value for p in step._p_tensors],
                    [b._value for b in step._b_tensors],
                    opt._state, jax.numpy.float32(0), jax.random.PRNGKey(0),
                    (ids._value,), (ids._value,)))
    kernels = count_kernels(step._compiled, sds, on_tpu,
                            want=3 * cfg.num_hidden_layers, compiled=False)
    emit(phase="train", model="llama_mid" if not args.tiny else "tiny",
         params=model.num_params(), batch=batch, seq=seq, steps=len(losses),
         losses=[round(x, 4) for x in losses],
         setup_s={"build_and_first_step": round(t_first, 2)},
         run_s=round(t_run, 2), tpu_custom_calls=kernels,
         peak_bytes_in_use=peak_bytes())


# ---------------------------------------------------------------------------
# four chips: tensor-parallel serving against one device
# ---------------------------------------------------------------------------

def prefill_logits(dec, prompt):
    """Last-position logits of one prompt through the decoder's own
    prefill program (tests/test_tp_serving.py measures tp against one
    device the same way)."""
    import numpy as np
    cache, seq = dec.cache, 1 << 30
    ids = np.asarray(prompt, np.int32)[None]
    cache.allocate(seq, ids.shape[1] + 1)
    slots = np.asarray([[cache.extend(seq) for _ in range(ids.shape[1])]],
                       np.int32)
    logits, cache.k, cache.v = dec._prefill(dec.weights, cache.k, cache.v,
                                            ids, slots)
    cache.free(seq)
    return np.asarray(logits, np.float32)[0]


def phase_tp4(args, dev):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    cfg, geom = serve_config(args.tiny, layers=8)
    prompts = make_prompts(args.seed, cfg.vocab_size, geom["prompt_lo"],
                           geom["prompt_hi"])
    n_probe = geom["bucket"] // 4       # 512 at full size: flash kernel
    probe = make_prompts(args.seed + 1, cfg.vocab_size, n_probe, n_probe)[0]
    devices = jax.devices()[:4]
    tokens, logits, report = {}, {}, {}
    for name, mesh in (("one_device", None),
                       ("tp4", Mesh(np.asarray(devices), ("tp",)))):
        t0 = time.perf_counter()
        eng = build_engine(cfg, geom, args.seed, None, mesh=mesh)
        t_build = time.perf_counter() - t0
        if mesh is None:
            pool = eng.stats()["kv_pool_bytes"]
            weights = sum(x.nbytes for x in
                          jax.tree.leaves(eng.dec.weights))
        t0 = time.perf_counter()
        tokens[name] = serve_requests(eng, prompts, cfg.vocab_size)
        t_run = time.perf_counter() - t0
        logits[name] = prefill_logits(eng.dec, probe)
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices]
        report[name] = {
            "attention": eng.attention_impls["ragged"],
            "setup_s": round(t_build, 2), "run_s": round(t_run, 2),
            "compiles": eng.compile_watch.compiles,
            "bytes_in_use": in_use}
        check(eng.attention_impls["ragged"] == "pallas"
              or dev["platform"] != "tpu",
              f"{name} would serve through {eng.attention_impls['ragged']}")
        if mesh is not None and in_use[0] is not None:
            share = (weights + pool) / 4
            check(max(in_use) <= 2 * share,
                  f"a device holds {max(in_use)} bytes, more than twice "
                  f"its quarter ({share:.0f}) of weights plus pool")
        eng.close()
        del eng
        release_device_memory()
    one, tp4 = tokens["one_device"], tokens["tp4"]
    prefix = [int((np.cumsum(one[i] != tp4[i]) == 0).sum())
              for i in range(N_REQUESTS)]
    a, b = logits["one_device"], logits["tp4"]
    rel = float(np.abs(a - b).max() / np.abs(a).max())
    cos = float((a * b).sum() / (np.linalg.norm(a) * np.linalg.norm(b)))
    emit(phase="tp4", layers=cfg.num_hidden_layers, hidden=cfg.hidden_size,
         weight_bytes=weights, kv_pool_bytes=pool,
         requests_identical=sum(n == MAX_NEW for n in prefix),
         identical_prefix_tokens=prefix, logits_rel_err=rel,
         logits_cosine=cos, **report)
    # Greedy tokens are reported, the logits decide. Each tp shard rounds
    # its partial product to bf16 before the block's allreduce, so at
    # this width 8 layers differ from one device by ~2% of the largest
    # logit on ANY backend (0.0206 on the CPU, 0.0212 on the v5e, cosine
    # 0.9998 both) and a 128k-way argmax over random weights flips within
    # a few tokens. The 2% of tests/test_tp_serving.py is an f32 bound; a
    # misplaced head or shard gives a cosine far below 0.999.
    check(np.isfinite(rel) and rel < 0.05 and cos > 0.999,
          f"tp=4 logits off by {rel:.4f} of the largest logit, cosine "
          f"{cos:.5f}; identical token prefixes {prefix}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths, for the CPU rehearsal")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = None
    try:
        dev = device_info()
        check_device(dev, args.chips)
        emit(phase="start", device=dev, chips=args.chips, tiny=args.tiny,
             compile_cache=os.environ["JAX_COMPILATION_CACHE_DIR"])
        if args.chips == 4:
            phase_tp4(args, dev)
        else:
            phase_serve(args, dev)
            emit(phase="between", live_array_bytes=release_device_memory())
            phase_train(args, dev)
    except BaseException as e:      # noqa: BLE001 — reported, never passed
        traceback.print_exc()
        emit(ok=False, device=dev, error=f"{type(e).__name__}: {e}"[:2000])
        return 1
    emit(ok=True, device=dev)
    return 0


if __name__ == "__main__":
    sys.exit(main())
