"""Per-program communication audit: abstract-trace every distributed
entry point under an 8-device mesh and report each collective's kind,
axis, per-shard payload bytes, and count per dispatch.

This is the regression net ROADMAP item 1 (multi-chip TP serving) ships
under: the per-layer allreduce is about to become the serving hot path,
and an accidental implicit all-gather — or a doubled allreduce from a
refactor — is invisible to every numeric test (the math stays right,
the step just gets slower). The audit walks the traced jaxpr, so it
counts exactly what the program will execute:

- ``scan`` bodies multiply by the trip count (a per-tick ppermute in an
  n-tick pipeline counts n times);
- ``cond``/``switch`` branches merge by elementwise max (the worst-case
  schedule);
- ``while`` bodies count ONCE and the program is marked approximate.

Entry points: the eager collective bodies (collective.py — the SAME
module-level body functions the public API jits; the EQuARX-style
int8_all_reduce included), ring attention forward/backward (zigzag and
the multi-axis fallback), the GPipe pipeline, the table-driven 1F1B
schedule, the full 4D-parallel pipelined-Llama train step, and (ISSUE
8) the TENSOR-PARALLEL SERVING STEP — the ServingEngine(tp=2) ragged
[T, W] program, fp32 and int8 comms, whose expectations pin exactly
one allreduce per attention/MLP block per layer per ministep, one
logits all_gather per ministep, and ZERO collectives on the KV-append
path (any implicit gather there would change the counts).

The committed expectations file (tools/flightcheck/comm_expectations.json)
pins every program's audit; ``python -m tools.flightcheck.comm_audit``
fails on ANY drift. Regenerate deliberately with ``--write`` after a
reviewed change.
"""
from __future__ import annotations

import json
import os
import sys
from collections import Counter
from typing import Dict, List, Optional, Tuple

EXPECTATIONS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "comm_expectations.json")

# data-moving collective primitives (axis_index/pvary move nothing)
COMM_PRIMS = {"psum", "pmax", "pmin", "ppermute", "pshuffle",
              "all_gather", "all_to_all", "psum_scatter",
              "reduce_scatter", "pbroadcast"}

_N_DEV = 8


def ensure_devices(n: int = _N_DEV):
    """Force an n-device CPU backend (the conftest dance, usable
    standalone): must run before anything initializes a jax backend."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax._src import xla_bridge as _xb
    if not _xb.backends_are_initialized():
        os.environ["JAX_PLATFORMS"] = "cpu"
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    if len(jax.devices()) < n:
        raise RuntimeError(
            f"comm audit needs {n} devices, found {len(jax.devices())} "
            f"(backend initialized too early?)")


# -- jaxpr walking ----------------------------------------------------------

def _axis_of(params) -> str:
    ax = params.get("axes", params.get("axis_name"))
    if isinstance(ax, (tuple, list)):
        return ",".join(str(a) for a in ax)
    return str(ax)


def _nbytes(eqn) -> int:
    import numpy as np
    total = 0
    for v in eqn.invars:
        if hasattr(v, "val"):        # literal
            continue
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            total += int(np.prod(aval.shape, dtype=np.int64)
                         * np.dtype(aval.dtype).itemsize)
    return total


def _walk(jx, mult: int, acc: Counter, flags: set):
    for eqn in jx.eqns:
        prim = eqn.primitive.name
        if prim in COMM_PRIMS:
            axis = _axis_of(eqn.params)
            if axis:    # psum(axes=()) appears in transposed shard_map
                acc[(prim, axis, _nbytes(eqn))] += mult  # bodies; no-op
            continue
        if prim == "scan":
            _walk(eqn.params["jaxpr"].jaxpr,
                  mult * int(eqn.params["length"]), acc, flags)
            continue
        if prim == "while":
            flags.add("while-approx")   # trip count unknown: count once
            _walk(eqn.params["body_jaxpr"].jaxpr, mult, acc, flags)
            _walk(eqn.params["cond_jaxpr"].jaxpr, mult, acc, flags)
            continue
        if prim in ("cond", "switch"):
            best: Counter = Counter()
            for br in eqn.params["branches"]:
                c: Counter = Counter()
                _walk(br.jaxpr, mult, c, flags)
                for k, v in c.items():
                    best[k] = max(best[k], v)
            for k, v in best.items():
                acc[k] += v
            continue
        for v in eqn.params.values():
            _recurse(v, mult, acc, flags)


def _recurse(v, mult, acc, flags):
    core = getattr(v, "jaxpr", None)
    if core is not None and hasattr(core, "eqns"):
        _walk(core, mult, acc, flags)
    elif hasattr(v, "eqns"):
        _walk(v, mult, acc, flags)
    elif isinstance(v, (tuple, list)):
        for s in v:
            _recurse(s, mult, acc, flags)


def audit_jaxpr(closed_jaxpr) -> Tuple[List[dict], List[str]]:
    """-> (rows sorted by (kind, axis, bytes), approximation flags).
    Row: {kind, axis, bytes (per-shard payload), count (per dispatch)}."""
    acc: Counter = Counter()
    flags: set = set()
    _walk(closed_jaxpr.jaxpr, 1, acc, flags)
    rows = [{"kind": k, "axis": a, "bytes": b, "count": int(n)}
            for (k, a, b), n in acc.items()]
    rows.sort(key=lambda r: (r["kind"], r["axis"], r["bytes"]))
    return rows, sorted(flags)


# -- entry-point registry ---------------------------------------------------

def _mesh1d(name="rank", n=_N_DEV):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.asarray(jax.devices()[:n]), (name,))


def _collective_program(body, out_spec, shape, in_spec=None):
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh = _mesh1d()
    f = shard_map(body, mesh=mesh, in_specs=(in_spec or P("rank"),),
                  out_specs=out_spec, check_vma=False)
    return f, (jax.ShapeDtypeStruct(shape, jnp.float32),)


def _build_collectives():
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed import collective as C
    n = _N_DEV
    ring = [(i, (i + 1) % n) for i in range(n)]
    return {
        "collective.all_reduce": lambda: _collective_program(
            C.all_reduce_body(C.ReduceOp.SUM), P("rank"), (n, 64, 64)),
        "collective.all_gather": lambda: _collective_program(
            C.all_gather_body(), P(), (n, 64, 64)),
        "collective.broadcast": lambda: _collective_program(
            C.broadcast_body(0), P("rank"), (n, 64, 64)),
        "collective.reduce": lambda: _collective_program(
            C.reduce_body(C.ReduceOp.SUM, 0), P("rank"), (n, 64, 64)),
        "collective.reduce_scatter": lambda: _collective_program(
            C.reduce_scatter_body(), P("rank"), (n, n)),
        "collective.all_to_all": lambda: _collective_program(
            C.all_to_all_body(), P("rank"), (n, n, 16)),
        "collective.barrier": lambda: _collective_program(
            C.barrier_body(), P("rank"), (n,)),
        "collective.p2p_ring": lambda: _collective_program(
            C.ppermute_body(ring), P("rank"), (n, 64, 64)),
        # the EQuARX-style quantized allreduce (ISSUE 8): its exact
        # collective shape — TWO all_to_alls (int8 chunks + their
        # per-row scales, the reduce-scatter phase) + TWO all_gathers
        # (reduced int8 chunks + fresh scales) — is pinned here so a
        # refactor that silently doubles a phase (or falls back to
        # fp32 psum) fails the gate
        "collective.int8_all_reduce": lambda: _collective_program(
            C.int8_all_reduce_body(n), P("rank"), (n, 4, 64)),
    }


def _build_ring_attention():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.distributed.ring_attention import ring_attention

    def fwd():
        mesh = _mesh1d("sep")
        q = jax.ShapeDtypeStruct((1, 128, 4, 16), jnp.float32)
        return (lambda a, b, c: ring_attention(
            a, b, c, mesh, axis="sep", use_pallas=False)), (q, q, q)

    def grad():
        mesh = _mesh1d("sep")
        q = jax.ShapeDtypeStruct((1, 128, 4, 16), jnp.float32)

        def loss(a, b, c):
            return ring_attention(a, b, c, mesh, axis="sep",
                                  use_pallas=False).sum()
        return jax.grad(loss, argnums=(0, 1, 2)), (q, q, q)

    def multiaxis():
        import jax as _j
        mesh = Mesh(np.asarray(_j.devices()[:8]).reshape(2, 4),
                    ("dp", "sep"))
        q = jax.ShapeDtypeStruct((2, 64, 4, 16), jnp.float32)
        return (lambda a, b, c: ring_attention(
            a, b, c, mesh, axis="sep", use_pallas=False)), (q, q, q)

    return {"ring_attention.zigzag_fwd": fwd,
            "ring_attention.zigzag_grad": grad,
            "ring_attention.multiaxis_fwd": multiaxis}


def _build_pipelines():
    import jax
    import jax.numpy as jnp

    def gpipe():
        from paddle_tpu.distributed.fleet.pipeline import pipeline_apply
        mesh = _mesh1d("pp")
        d, m, b = 16, 8, 4
        w = jax.ShapeDtypeStruct((8, d, d), jnp.float32)
        xs = jax.ShapeDtypeStruct((m, b, d), jnp.float32)
        return (lambda wp, x: pipeline_apply(
            lambda p, a: jnp.tanh(a @ p), wp, x, mesh)), (w, xs)

    def onef1b():
        from paddle_tpu.distributed.fleet.pp_schedule import (
            build_pipeline_schedule, make_pipeline_loss_fn)
        mesh = _mesh1d("pp")
        d, m, b, p = 16, 8, 4, 8
        sched = build_pipeline_schedule(p, m, 1, "1F1B")

        def stage_fn(pj, x):
            return jnp.tanh(x @ pj["w"])

        def loss_fn(lp, out, y):
            return jnp.mean((out * lp - y) ** 2)

        ploss = make_pipeline_loss_fn(stage_fn, loss_fn, mesh, sched)
        sp = {"w": jax.ShapeDtypeStruct((1, p, d, d), jnp.float32)}
        lp = jax.ShapeDtypeStruct((d,), jnp.float32)
        xs = jax.ShapeDtypeStruct((m, b, d), jnp.float32)
        ys = jax.ShapeDtypeStruct((m, b, d), jnp.float32)
        return ploss, (sp, lp, xs, ys)

    return {"pipeline.gpipe": gpipe, "pp_schedule.1f1b": onef1b}


def _build_llama_pp():
    def step():
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh
        from paddle_tpu.models.llama_pp import (PipelinedLlamaConfig,
                                                build_pipelined_llama_step)
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 2, 2),
                    ("pp", "mp", "dp"))
        cfg = PipelinedLlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            num_attention_heads=4, num_key_value_heads=2,
            layers_per_chunk=1, vpp_degree=1, max_seq_len=32)
        n_micro, micro_b, seq = 4, 2, 16
        state, step_fn, _ = build_pipelined_llama_step(
            cfg, mesh, n_micro, micro_b, seq)
        ids = jnp.zeros((n_micro * micro_b, seq), jnp.int32)
        return step_fn, (state, ids, ids)

    return {"llama_pp.train_step": step}


def _build_tp_serving():
    """The ISSUE-8 serving-step programs: the unified ragged [T, W]
    chunk of a ServingEngine(tp=2) on a 2-device submesh, fp32 and
    int8 comms. The pinned expectations ARE the TP contract:

    - fp32: exactly ONE psum per attention/MLP block per layer per
      ministep (T * layers * 2 in total) plus ONE logits all_gather
      per ministep — and NOTHING else: the KV-append path
      (reshape_and_cache into the kv-head-sharded pool) contributes
      zero collectives, and a doubled/implicit collective from a
      refactor changes the counts and fails this gate in ~4s, not in
      a profile;
    - int8: each block psum becomes the quantized collective
      (2 all_to_alls + 2 all_gathers, chunks + per-row scales), the
      logits gather stays exact;
    - spec (ISSUE 9): the speculative VERIFY program
      (serving.ragged_spec_tp2) must have exactly the T=1 ragged
      program's collectives — one psum per block per layer plus one
      logits all_gather. In-program acceptance compares post-gather
      (replicated) tokens and the rejected-tail neutralization
      zero-scatters each shard's own kv-head slice, so verification
      adds ZERO collectives; any new collective here fails the gate.
    """
    def _mk(tp_comm, kv_quant=None):
        def build():
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh
            from paddle_tpu.inference.paged_decode import \
                PagedLlamaDecoder
            from paddle_tpu.inference.serving import ServingEngine
            from paddle_tpu.models.llama import LlamaConfig
            cfg = LlamaConfig(
                vocab_size=64, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=64)
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
            dec = PagedLlamaDecoder.from_config(
                cfg, num_blocks=8, block_size=4, mesh=mesh,
                mp_axis="tp", tp_shard_map=True, tp_comm=tp_comm,
                kv_quant=kv_quant)
            eng = ServingEngine(dec, tp=2, tp_comm=tp_comm,
                                max_batch_size=2,
                                prompt_buckets=(8, 16), chunk_size=2,
                                prefill_chunk=4)
            T, W = 2, 4
            S = jax.ShapeDtypeStruct
            i32, f32 = jnp.int32, jnp.float32
            args = (dec.weights, dec.cache.k, dec.cache.v,
                    S((T, W), i32), S((W,), i32), S((W,), i32),
                    S((W,), jnp.bool_), S((W,), i32),
                    S((T, W), i32), S((T, W), i32), S((T, W), i32),
                    S((T, W), i32), S((T, W), i32),
                    S((T, W), jnp.bool_),
                    S((eng.max_b + 1, dec.max_pages), i32),
                    S((T, W), f32), S((T, 2), jnp.uint32))
            return eng._ragged_j, args
        return build

    def _mk_spec():
        def build():
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh
            from paddle_tpu.inference.paged_decode import \
                PagedLlamaDecoder
            from paddle_tpu.inference.serving import ServingEngine
            from paddle_tpu.inference.spec_decode import SpecConfig
            from paddle_tpu.models.llama import LlamaConfig
            cfg = LlamaConfig(
                vocab_size=64, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=64)
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
            dec = PagedLlamaDecoder.from_config(
                cfg, num_blocks=8, block_size=4, mesh=mesh,
                mp_axis="tp", tp_shard_map=True, tp_comm="fp32")
            eng = ServingEngine(dec, tp=2, max_batch_size=2,
                                prompt_buckets=(8, 16), chunk_size=2,
                                prefill_chunk=4,
                                spec_decode=SpecConfig(draft_len=3))
            W = 8
            S = jax.ShapeDtypeStruct
            i32, f32 = jnp.int32, jnp.float32
            args = (dec.weights, dec.cache.k, dec.cache.v,
                    S((W,), i32), S((W,), jnp.bool_), S((W,), i32),
                    S((W,), i32), S((W,), i32), S((W,), i32),
                    S((W,), i32),
                    S((eng.max_b + 1, dec.max_pages), i32),
                    S((W,), f32), S((2,), jnp.uint32),
                    S((W,), i32), S((W,), jnp.bool_))
            return eng._spec_j, args
        return build

    def _mk_lora():
        def build():
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh
            from paddle_tpu.inference.lora import AdapterRegistry
            from paddle_tpu.inference.paged_decode import \
                PagedLlamaDecoder
            from paddle_tpu.inference.serving import ServingEngine
            from paddle_tpu.models.llama import LlamaConfig
            cfg = LlamaConfig(
                vocab_size=64, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=64)
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
            dec = PagedLlamaDecoder.from_config(
                cfg, num_blocks=8, block_size=4, mesh=mesh,
                mp_axis="tp", tp_shard_map=True, tp_comm="fp32")
            reg = AdapterRegistry(rank=2)
            reg.register_random("tenant0", seed=0)
            eng = ServingEngine(dec, tp=2, max_batch_size=2,
                                prompt_buckets=(8, 16), chunk_size=2,
                                prefill_chunk=4, lora=reg)
            T, W = 2, 4
            lay = reg.layout
            S = jax.ShapeDtypeStruct
            i32, f32 = jnp.int32, jnp.float32
            args = (dec.weights, dec.cache.k, dec.cache.v,
                    S((dec.cache.num_blocks, lay.page_elems), f32),
                    S((2,), i32),
                    S((eng.max_b + 1, lay.n_pages), i32),
                    S((T, W), i32), S((W,), i32), S((W,), i32),
                    S((W,), jnp.bool_), S((W,), i32),
                    S((T, W), i32), S((T, W), i32), S((T, W), i32),
                    S((T, W), i32), S((T, W), i32),
                    S((T, W), jnp.bool_),
                    S((eng.max_b + 1, dec.max_pages), i32),
                    S((T, W), f32), S((T, 2), jnp.uint32))
            return eng._ragged_lora_j, args
        return build

    def _mk_ms():
        def build():
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh
            from paddle_tpu.inference.paged_decode import \
                PagedLlamaDecoder
            from paddle_tpu.inference.serving import ServingEngine
            from paddle_tpu.models.llama import LlamaConfig
            cfg = LlamaConfig(
                vocab_size=64, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=64)
            mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
            dec = PagedLlamaDecoder.from_config(
                cfg, num_blocks=8, block_size=4, mesh=mesh,
                mp_axis="tp", tp_shard_map=True, tp_comm="fp32")
            eng = ServingEngine(dec, tp=2, tp_comm="fp32",
                                multi_step=4, max_batch_size=2,
                                prompt_buckets=(8, 16), chunk_size=2,
                                prefill_chunk=4)
            # the fused window: k * chunk_size ministeps in ONE
            # program (the shapes the scheduler dispatches when every
            # running slot is decoding), plus the per-column eos ids
            # the on-device finish bookkeeping consumes
            T, W = 4 * 2, 4
            S = jax.ShapeDtypeStruct
            i32, f32 = jnp.int32, jnp.float32
            args = (dec.weights, dec.cache.k, dec.cache.v,
                    S((T, W), i32), S((W,), i32), S((W,), i32),
                    S((W,), jnp.bool_), S((W,), i32),
                    S((T, W), i32), S((T, W), i32), S((T, W), i32),
                    S((T, W), i32), S((T, W), i32),
                    S((T, W), jnp.bool_),
                    S((eng.max_b + 1, dec.max_pages), i32),
                    S((T, W), f32), S((T, 2), jnp.uint32),
                    S((W,), i32))
            return eng._ragged_ms_j, args
        return build

    def _mk_dp():
        def build():
            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh
            from paddle_tpu.distributed.spec_layout import SpecLayout
            from paddle_tpu.inference.fleet import Router
            from paddle_tpu.inference.paged_decode import \
                PagedLlamaDecoder
            from paddle_tpu.inference.serving import ServingEngine
            from paddle_tpu.models.llama import LlamaConfig
            cfg = LlamaConfig(
                vocab_size=64, hidden_size=32, intermediate_size=64,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=64)

            def factory(idx, devs):
                mesh = Mesh(np.asarray(devs), ("tp",))
                dec = PagedLlamaDecoder.from_config(
                    cfg, num_blocks=8, block_size=4, mesh=mesh,
                    mp_axis="tp", tp_shard_map=True, tp_comm="fp32")
                return ServingEngine(dec, tp=2, max_batch_size=2,
                                     prompt_buckets=(8, 16),
                                     chunk_size=2, prefill_chunk=4)

            router = Router(None, dp=2, tp=2, engine_factory=factory)
            # replica 1 — the row OFF the default device slice: its
            # placement comes from SpecLayout.fleet_device_slices and
            # proves a non-zero dp row's step program is byte-for-byte
            # the single-engine tp program
            eng = router.replicas[1].engine
            grid = SpecLayout().fleet_device_slices(2, 2)
            assert list(eng.dec.mesh.devices.ravel()) == grid[1]
            T, W = 2, 4
            S = jax.ShapeDtypeStruct
            i32, f32 = jnp.int32, jnp.float32
            args = (eng.dec.weights, eng.dec.cache.k, eng.dec.cache.v,
                    S((T, W), i32), S((W,), i32), S((W,), i32),
                    S((W,), jnp.bool_), S((W,), i32),
                    S((T, W), i32), S((T, W), i32), S((T, W), i32),
                    S((T, W), i32), S((T, W), i32),
                    S((T, W), jnp.bool_),
                    S((eng.max_b + 1, eng.dec.max_pages), i32),
                    S((T, W), f32), S((T, 2), jnp.uint32))
            return eng._ragged_j, args
        return build

    return {"serving.ragged_tp2_fp32": _mk("fp32"),
            "serving.ragged_tp2_int8": _mk("int8"),
            # ISSUE 13: the QUANTIZED-POOL ragged step must pin
            # byte-identical collectives to the fp32-pool program —
            # the int8 planes' sidecar scales shard dim-aligned with
            # their kv heads (canonical cache_k_scale spec), so the
            # quantize-at-append scatter and dequant-at-read gather
            # are both shard-local; ANY implicit gather over the
            # scales (a mis-sharded sidecar) changes these counts and
            # fails the 4s gate
            "serving.ragged_kv8_tp2": _mk("fp32", kv_quant="int8"),
            # ISSUE 16: the multi-step fused window at k=4 must pin
            # EXACTLY k x the per-ministep collectives of the T=2
            # baseline above (4x the T, 4x the psums and logits
            # gathers, nothing else): the scan carry (sampled tokens,
            # live mask, KV pool planes) is shard-local, the
            # on-device EOS bookkeeping compares post-gather
            # replicated tokens, and the per-iteration KV append
            # stays collective-free — a refactor that syncs the
            # carry or double-gathers logits changes these counts
            # and fails the 4s gate
            "serving.ragged_k4_tp2": _mk_ms(),
            "serving.ragged_spec_tp2": _mk_spec(),
            # ISSUE 11: a dp x tp FLEET replica's ragged step — built
            # through the Router on row 1 of the SpecLayout 2x2 device
            # grid — must pin EXACTLY the collectives of the
            # single-engine tp=2 program (serving.ragged_tp2_fp32):
            # data parallelism contributes ZERO step-path collectives
            # because replicas never talk during a step (affinity is a
            # host-side hash lookup, failover a host-side re-enqueue)
            "serving.ragged_dp2_tp2": _mk_dp(),
            # ISSUE 10: the multi-tenant lora twin of the fp32 ragged
            # step MUST pin exactly the base program's collectives —
            # the per-row adapter deltas (replicated pool gather,
            # per-shard A-row/B-column slices, row-parallel deltas
            # joining the partial product before the block psum) add
            # ZERO collectives; any new psum/all_gather here fails
            # the gate
            "serving.ragged_lora_tp2": _mk_lora()}


def programs() -> Dict[str, callable]:
    """name -> lazy builder returning (traceable fn, example args).
    Builders import jax/paddle_tpu only when called."""
    out: Dict[str, callable] = {}
    out.update(_build_collectives())
    out.update(_build_ring_attention())
    out.update(_build_pipelines())
    out.update(_build_llama_pp())
    out.update(_build_tp_serving())
    return out


def program_names() -> List[str]:
    return sorted(programs())


# -- audit / expectations ---------------------------------------------------

def audit(only: Optional[str] = None) -> Dict[str, dict]:
    """Trace and audit every registered program (or those whose name
    starts with ``only``). -> {name: {"collectives": rows, "flags":
    [...]}}; a trace failure becomes {"error": ...}."""
    ensure_devices()
    import jax
    report: Dict[str, dict] = {}
    for name, build in sorted(programs().items()):
        if only and not name.startswith(only):
            continue
        try:
            fn, args = build()
            jx = jax.make_jaxpr(fn)(*args)
            rows, flags = audit_jaxpr(jx)
            report[name] = {"collectives": rows, "flags": flags}
        except Exception as e:   # a program that cannot trace IS a bug
            report[name] = {"error": f"{type(e).__name__}: {e}"}
    return report


def save(report: Dict[str, dict], path: str = EXPECTATIONS):
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1, sort_keys=True)
        fh.write("\n")


def load(path: str = EXPECTATIONS) -> Dict[str, dict]:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def compare(actual: Dict[str, dict],
            expected: Dict[str, dict]) -> List[str]:
    """Human-readable drift list (empty = match). Only programs present
    in ``actual`` are compared (supports scoped runs), but a program
    expected and not even REGISTERED is drift."""
    problems: List[str] = []
    names = set(programs())
    for name in sorted(set(expected) - names):
        problems.append(f"{name}: expected but no longer registered")
    for name, got in sorted(actual.items()):
        want = expected.get(name)
        if want is None:
            problems.append(f"{name}: not in expectations file "
                            f"(regenerate with --write)")
            continue
        if "error" in got:
            problems.append(f"{name}: TRACE FAILURE {got['error']}")
            continue
        if got != want:
            problems.append(
                f"{name}: communication drift\n"
                f"    expected: {json.dumps(want.get('collectives'))}\n"
                f"    actual:   {json.dumps(got.get('collectives'))}")
    return problems


def format_report(report: Dict[str, dict]) -> str:
    lines = []
    for name, entry in sorted(report.items()):
        if "error" in entry:
            lines.append(f"{name}: TRACE FAILURE {entry['error']}")
            continue
        rows = entry["collectives"]
        flag = (" [" + ",".join(entry["flags"]) + "]"
                if entry.get("flags") else "")
        if not rows:
            lines.append(f"{name}: no collectives{flag}")
            continue
        lines.append(f"{name}:{flag}")
        for r in rows:
            lines.append(f"    {r['kind']:<14} axis={r['axis']:<8} "
                         f"{r['bytes']:>10} B  x{r['count']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m tools.flightcheck.comm_audit",
        description="jaxpr-level communication audit of the "
                    "distributed entry points")
    ap.add_argument("--write", action="store_true",
                    help="regenerate the committed expectations file")
    ap.add_argument("--only", default=None,
                    help="audit only programs with this name prefix")
    args = ap.parse_args(argv)

    report = audit(only=args.only)
    if args.only and not report:
        print(f"comm audit: --only {args.only!r} matches no registered "
              f"program; known: {', '.join(program_names())}",
              file=sys.stderr)
        return 2
    print(format_report(report))
    errors = [n for n, e in report.items() if "error" in e]
    if args.write:
        if errors:
            print(f"comm audit: NOT writing expectations — "
                  f"{len(errors)} trace failure(s)")
            return 1
        if args.only:
            merged = load() if os.path.exists(EXPECTATIONS) else {}
            merged.update(report)
            report = merged
        save(report)
        print(f"comm audit: expectations written -> {EXPECTATIONS}")
        return 0
    if not os.path.exists(EXPECTATIONS):
        print("comm audit: no expectations file committed — run with "
              "--write")
        return 1
    problems = compare(report, load())
    if problems:
        print("\ncomm audit: DRIFT detected")
        for p in problems:
            print("  " + p)
        return 1
    print(f"\ncomm audit: {len(report)} program(s) match the committed "
          f"expectations")
    return 0


if __name__ == "__main__":
    sys.exit(main())
