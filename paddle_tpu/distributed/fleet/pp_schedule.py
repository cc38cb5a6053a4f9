"""True pipeline-parallel schedules (1F1B / interleaved-1F1B / FThenB) as
table-driven SPMD programs.

Reference parity: PipelineParallel.forward_backward_pipeline (1F1B,
/root/reference/python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py:440), PipelineParallelWithInterleave (:906), FThenB
(:1489), and the schedule passes (passes/pipeline_scheduler_pass.py:48).

TPU-native design — no actor runtime, no p2p protocol:
- The *entire* schedule is static (n_micro, n_stages, vpp are compile-time
  constants), so we compute it host-side: for every (tick, stage) the
  tables say which chunk to forward, which to backward, which buffer slot
  each activation/gradient lives in. The device program is one
  `lax.scan` over the tick tables inside a `shard_map` that is manual
  over the 'pp' mesh axis only (tp/dp/fsdp compose as GSPMD auto axes).
- Forward activations hop stage s -> s+1 (ring ppermute, wrapping
  (p-1) -> 0 advances a microbatch to its next virtual-chunk round);
  gradients hop the reverse ring.
- Backward rematerializes the chunk forward from its saved *input* (the
  1F1B memory story: the act buffer holds at most O(n_stages [* vpp])
  in-flight microbatch inputs, never O(n_micro) — compare FThenB where
  it provably holds O(n_micro * vpp); see `PipelineSchedule.act_buf_size`).
- The last virtual chunk computes the loss and its gradient seed in the
  forward slot, so the backward wave starts the same tick (true 1F1B
  pairing, not fwd-all-then-bwd-all).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

__all__ = ["PipelineSchedule", "build_pipeline_schedule",
           "pipeline_forward_backward", "make_pipeline_loss_fn"]


_MODES = {
    "fthenb": "fthenb", "gpipe": "fthenb", "f-then-b": "fthenb",
    "1f1b": "1f1b", "vpp": "1f1b", "interleave": "1f1b",
    "interleaved": "1f1b", "1f1b-interleave": "1f1b",
    # zero-bubble (ZB-H1 family): the backward splits into an
    # input-grad slot B (critical path) and a weight-grad slot W that
    # the scheduler defers into would-be bubble ticks. The reference
    # has no such schedule (pipeline_scheduler_pass.py:48 stops at
    # 1F1B/VPP); store-activations mode only — under jax.vjp, a
    # dx-only call DCEs the dw matmuls and vice versa, so B and W cost
    # ~1 forward each with shared residuals.
    "zb": "zb", "zb1": "zb", "zero-bubble": "zb", "zbh1": "zb",
}


@dataclass
class PipelineSchedule:
    """Static tick tables for one (n_stages, n_micro, vpp, mode) config.

    All tables are int32/bool ndarrays of shape [n_ticks, n_stages]."""
    n_stages: int
    n_micro: int
    vpp: int
    mode: str
    n_ticks: int
    act_buf_size: int
    grad_buf_size: int
    tables: Dict[str, np.ndarray] = field(repr=False)
    # store-activations mode: vjp-residual slots (write fwd-tick, read
    # bwd-tick). Defaulted for schedules built before this field existed.
    res_buf_size: int = 1

    # Tick cost model (single-chunk-forward units). The engine cond-
    # skips invalid slots (pipeline_forward_backward wraps the fwd and
    # bwd compute in lax.cond on the per-stage validity bits), so a tick
    # costs what its *busiest stage* actually runs: fwd = 1; bwd = 2
    # from stored residuals, 3 under remat (remat-fwd 1 + bwd 2). The
    # lock-step barrier is the per-tick ppermute pair, hence max over
    # stages. A model in chunk units, not measured on the chip.
    CHUNK_COST_PER_TICK = 4.0          # full fwd+bwd tick, remat (back-compat)

    def chunk_cost_per_tick(self, remat: bool = True) -> float:
        return 4.0 if remat else 3.0

    def tick_costs(self, remat: bool = True) -> np.ndarray:
        """Per-tick wall cost [n_ticks]: max over stages of the work the
        cond-skipping engine actually executes that tick."""
        if self.mode == "zb":
            # store-mode units: fwd 1, input-grad B 1, weight-grad W 1
            per_stage = (self.tables["fwd_valid"].astype(np.float64)
                         + self.tables["bwd_valid"].astype(np.float64)
                         + self.tables["w_valid"].astype(np.float64))
        else:
            b = 3.0 if remat else 2.0
            per_stage = (self.tables["fwd_valid"].astype(np.float64)
                         + b * self.tables["bwd_valid"].astype(np.float64))
        return per_stage.max(axis=1)

    @property
    def work_units(self) -> float:
        """Total wall cost in single-chunk-forward units for the whole
        step (cond-skipping engine, remat mode)."""
        return float(self.tick_costs(remat=True).sum())

    def efficiency(self, remat: bool = True) -> float:
        """ideal / achieved wall ratio — 1.0 means no bubble. Ideal
        per-stage work is n_micro*vpp fwd + n_micro*vpp bwd."""
        if self.mode == "zb":
            ideal = self.n_micro * self.vpp * 3.0
        else:
            b = 3.0 if remat else 2.0
            ideal = self.n_micro * self.vpp * (1.0 + b)
        return ideal / float(self.tick_costs(remat).sum())

    def bubble_overhead(self, remat: bool = True) -> float:
        return 1.0 - self.efficiency(remat)

    def __hash__(self):  # identity — schedules are built once per step fn
        return id(self)


def build_pipeline_schedule(n_stages: int, n_micro: int, vpp: int = 1,
                            mode: str = "1F1B",
                            inflight_cap=None) -> PipelineSchedule:
    """Greedy dependency-respecting list scheduler.

    Work items: fwd(m, q) and bwd(m, q) for microbatch m and virtual stage
    q in [0, vpp*n_stages); virtual stage q lives on physical stage q % p
    (chunk j = q // p), so consecutive virtual stages are ring neighbors.
    Per tick each stage runs at most one fwd and one bwd item. A message
    (activation or gradient) sent at tick t is consumable from tick t+1.

    inflight_cap: per-stage in-flight microbatch limit (int, per-stage
    list, or None = auto). The lock-step tick runs one fwd AND one bwd
    slot, so a stage only reaches full throughput when enough
    microbatches are in flight to cover the fwd+bwd ring round-trip —
    2*(p-s)-1 at stage s. That is the v=1 auto default (it reaches the
    classic async-1F1B bubble (p-1)/(m+p-1) exactly, at ~2x the
    reference's p-deep in-flight window — cheap here because remat mode
    only holds chunk *inputs* in flight). Pass the Megatron depth
    (p - s) to reproduce the reference's tighter memory story at the
    cost of ~2x bubble. v>1 auto uses the Megatron interleave depth,
    which already reaches the classic bound."""
    p, m, v = int(n_stages), int(n_micro), int(vpp)
    mkey = _MODES.get(mode.lower())
    if mkey is None:
        raise ValueError(
            f"unknown pipeline schedule_mode {mode!r}; expected one of "
            f"{sorted(set(_MODES))}")
    if v > 1 and m % p != 0:
        raise ValueError(
            f"interleaved schedule needs n_micro ({m}) divisible by "
            f"n_stages ({p})")
    V = v * p
    stage_of = lambda q: q % p

    # --- greedy tick simulation -----------------------------------------
    fwd_tick: Dict[Tuple[int, int], int] = {}
    bwd_tick: Dict[Tuple[int, int], int] = {}
    stage_items = [[(mb, q) for q in range(V) if stage_of(q) == s
                    for mb in range(m)] for s in range(p)]
    # 1F1B in-flight cap on *injection* (q==0).
    if inflight_cap is not None:
        caps = [int(inflight_cap)] * p if np.isscalar(inflight_cap) \
            else [int(c) for c in inflight_cap]
        if len(caps) != p or min(caps) < 1:
            raise ValueError(
                f"inflight_cap must be a positive int or a length-{p} "
                f"per-stage list; got {inflight_cap!r}")
    elif v > 1:
        caps = [2 * (p - s - 1) + (v - 1) * p + 1 for s in range(p)]
    else:
        caps = [2 * (p - s) - 1 for s in range(p)]

    fwd_sched = [[] for _ in range(p)]   # per tick: list over stages
    bwd_sched = [[] for _ in range(p)]
    per_tick = []                        # [(fwd_sel, bwd_sel, w_sel)]
    w_tick: Dict[Tuple[int, int], int] = {}
    n_items = m * V
    t = 0
    limit = 6 * n_items + 8 * V + 64
    while len(bwd_tick) < n_items or \
            (mkey == "zb" and len(w_tick) < n_items):
        if t > limit:
            raise RuntimeError(
                f"pipeline scheduler failed to converge (p={p}, m={m}, "
                f"v={v}, mode={mkey}); scheduled {len(bwd_tick)}/{n_items}")
        fwd_sel: Dict[int, Tuple[int, int]] = {}
        for s in range(p):
            inflight = sum(1 for it in stage_items[s]
                           if it in fwd_tick and it not in bwd_tick)
            cands = []
            for it in stage_items[s]:
                if it in fwd_tick:
                    continue
                mb, q = it
                if q == 0:
                    if mkey == "1f1b" and inflight >= caps[s]:
                        continue
                elif fwd_tick.get((mb, q - 1), t) > t - 1:
                    continue
                cands.append(it)
            if cands:
                # deepest virtual stage first (drain), then oldest microbatch
                it = max(cands, key=lambda it: (it[1], -it[0]))
                fwd_sel[s] = it
                fwd_tick[it] = t
        all_fwd_done = len(fwd_tick) == n_items
        bwd_sel: Dict[int, Tuple[int, int]] = {}
        for s in range(p):
            cands = []
            for it in stage_items[s]:
                if it not in fwd_tick or it in bwd_tick:
                    continue
                mb, q = it
                if mkey == "fthenb" and not all_fwd_done:
                    continue
                if q == V - 1:
                    if fwd_tick[it] > t:       # seed ready same tick as fwd
                        continue
                elif bwd_tick.get((mb, q + 1), t) > t - 1:
                    continue
                cands.append(it)
            if cands:
                # oldest microbatch first, then deepest chunk
                it = min(cands, key=lambda it: (it[0], -it[1]))
                bwd_sel[s] = it
                bwd_tick[it] = t
        # zero-bubble W pass. Policy (swept over cap shapes on the
        # lock-step max-cost model): stage s runs W inline with fwd+B
        # (a 3-unit tick is bubble-free — ideal work IS 3 units/micro)
        # but keeps up to `s` W items deferred, exactly filling its
        # cooldown while the B-chain of the last microbatches drains
        # through shallower stages. p4/m16: bubble 0.158 (1F1B-store)
        # -> 0.111; the residual is the forced lock-step B drain (the
        # async-model ZB-H1 floor (p-1)/3m is not reachable here).
        w_sel: Dict[int, Tuple[int, int]] = {}
        if mkey == "zb":
            drained = (len(fwd_tick) == n_items
                       and len(bwd_tick) == n_items)
            for s in range(p):
                busy = (s in fwd_sel) + (s in bwd_sel)
                backlog = sum(1 for it in stage_items[s]
                              if bwd_tick.get(it, t + 1) <= t
                              and it not in w_tick)
                if busy >= 2 and not drained and backlog <= s:
                    continue
                cands = [it for it in stage_items[s]
                         if bwd_tick.get(it, t + 1) <= t
                         and it not in w_tick]
                if cands:
                    it = min(cands, key=lambda it: (it[0], -it[1]))
                    w_sel[s] = it
                    w_tick[it] = t
        per_tick.append((fwd_sel, bwd_sel, w_sel))
        t += 1
    n_ticks = t

    # --- static buffer-slot allocation ----------------------------------
    # act slot per (mb, q>=1): lives [arrival = fwd_tick[(mb,q-1)]+1,
    # bwd_tick[(mb,q)]]; grad slot per (mb, q): lives [seed/arrival tick,
    # bwd_tick[(mb,q)]]. Allocation is per stage (buffers are per-device).
    def _alloc(intervals):
        """intervals: {item: (stage, t_write, t_read)} -> (slots, size).

        A slot busy through t_read frees for writes at t_read + 1 (reads
        happen in the same tick's compute phase, after arrival writes)."""
        slots, size = {}, 0
        for s in range(p):
            evs = sorted((iv[1], iv[2], it) for it, iv in intervals.items()
                         if iv[0] == s)
            busy: list = []   # (t_read, slot)
            free: list = []
            next_slot = 0
            for t_w, t_r, it in evs:
                still = []
                for t_busy_until, b_slot in busy:
                    if t_busy_until >= t_w:
                        still.append((t_busy_until, b_slot))
                    else:
                        free.append(b_slot)
                busy = still
                if free:
                    slot = min(free)
                    free.remove(slot)
                else:
                    slot = next_slot
                    next_slot += 1
                busy.append((t_r, slot))
                slots[it] = slot
                size = max(size, slot + 1)
        return slots, size

    act_iv = {}
    for (mb, q), ft in fwd_tick.items():
        if q >= 1:
            act_iv[(mb, q)] = (stage_of(q), fwd_tick[(mb, q - 1)] + 1,
                               bwd_tick[(mb, q)])
    grad_iv = {}
    for (mb, q), bt in bwd_tick.items():
        t_w = fwd_tick[(mb, V - 1)] if q == V - 1 \
            else bwd_tick[(mb, q + 1)] + 1
        # zb: the incoming gradient is read again by the deferred
        # weight-grad slot, extending the slot's lifetime
        t_r = max(bt, w_tick.get((mb, q), bt))
        grad_iv[(mb, q)] = (stage_of(q), t_w, t_r)
    act_slot, act_size = _alloc(act_iv)
    grad_slot, grad_size = _alloc(grad_iv)
    # residual slots (store-activations mode): written at the fwd tick,
    # read at the bwd tick (and the W tick under zb) — every (mb, q)
    # including q == 0 (whose act input comes from xs, no act slot)
    res_iv = {(mb, q): (stage_of(q), ft,
                        max(bwd_tick[(mb, q)],
                            w_tick.get((mb, q), bwd_tick[(mb, q)])))
              for (mb, q), ft in fwd_tick.items()}
    res_slot, res_size = _alloc(res_iv)

    # --- emit tables -----------------------------------------------------
    def zi():
        return np.zeros((n_ticks, p), np.int32)

    def zb():
        return np.zeros((n_ticks, p), bool)

    T = {k: zi() for k in
         ("fwd_chunk", "fwd_mb", "fwd_in_slot", "fwd_seed_slot",
          "rx_slot", "grx_slot", "bwd_chunk", "bwd_mb", "bwd_in_slot",
          "bwd_gslot", "res_slot", "bwd_res_slot")}
    T.update({k: zb() for k in
              ("fwd_valid", "fwd_is_first", "fwd_is_last", "rx_valid",
               "grx_valid", "bwd_valid", "bwd_is_first")})
    if mkey == "zb":
        T.update({k: zi() for k in ("w_chunk", "w_mb", "w_res_slot",
                                    "w_gslot")})
        T["w_valid"] = zb()
    for tick, (fwd_sel, bwd_sel, w_sel) in enumerate(per_tick):
        for s, (mb, q) in w_sel.items():
            T["w_valid"][tick, s] = True
            T["w_chunk"][tick, s] = q // p
            T["w_mb"][tick, s] = mb
            T["w_res_slot"][tick, s] = res_slot[(mb, q)]
            T["w_gslot"][tick, s] = grad_slot[(mb, q)]
        for s, (mb, q) in fwd_sel.items():
            T["fwd_valid"][tick, s] = True
            T["fwd_chunk"][tick, s] = q // p
            T["fwd_mb"][tick, s] = mb
            T["fwd_is_first"][tick, s] = q == 0
            T["fwd_is_last"][tick, s] = q == V - 1
            if q >= 1:
                T["fwd_in_slot"][tick, s] = act_slot[(mb, q)]
            T["res_slot"][tick, s] = res_slot[(mb, q)]
            if q == V - 1:
                T["fwd_seed_slot"][tick, s] = grad_slot[(mb, q)]
            # receiver-side arrival of this fwd's output (next virtual stage)
            if q + 1 <= V - 1:
                rs, rt = stage_of(q + 1), tick + 1
                T["rx_valid"][rt, rs] = True
                T["rx_slot"][rt, rs] = act_slot[(mb, q + 1)]
        for s, (mb, q) in bwd_sel.items():
            T["bwd_valid"][tick, s] = True
            T["bwd_chunk"][tick, s] = q // p
            T["bwd_mb"][tick, s] = mb
            T["bwd_is_first"][tick, s] = q == 0
            if q >= 1:
                T["bwd_in_slot"][tick, s] = act_slot[(mb, q)]
            T["bwd_res_slot"][tick, s] = res_slot[(mb, q)]
            T["bwd_gslot"][tick, s] = grad_slot[(mb, q)]
            if q >= 1:  # this bwd's dx arrives at the upstream stage
                rs, rt = stage_of(q - 1), tick + 1
                T["grx_valid"][rt, rs] = True
                T["grx_slot"][rt, rs] = grad_slot[(mb, q - 1)]

    # sanity: every fwd/bwd read happens at/after its write
    for (mb, q), ft in fwd_tick.items():
        if q >= 1:
            assert fwd_tick[(mb, q - 1)] + 1 <= ft, (mb, q)
        assert bwd_tick[(mb, q)] >= ft, (mb, q)

    return PipelineSchedule(
        n_stages=p, n_micro=m, vpp=v, mode=mkey, n_ticks=n_ticks,
        act_buf_size=max(1, act_size), grad_buf_size=max(1, grad_size),
        res_buf_size=max(1, res_size), tables=T)


def _resolve_mesh(mesh):
    return mesh.to_jax_mesh() if hasattr(mesh, "to_jax_mesh") else mesh


def partial_manual_ok() -> bool:
    """Whether this jax can run a shard_map that is manual over a SUBSET
    of mesh axes and contains collectives. On jax 0.4.x the SPMD
    partitioner hard-CHECKs (spmd_partitioner.cc:512
    `target.IsManualSubgroup() == sharding().IsManualSubgroup()`) when a
    ppermute/all_gather sits in a partially-manual region of a mesh with
    auto axes — a fatal process abort, not a catchable error, so this is
    version-gated rather than probed. When False, the pipeline engines
    run the shard_map FULLY manual over every mesh axis: all in/out
    specs only name the pp axis, so non-pp shards are replicated at the
    boundary and the numerics are identical (auto-axis GSPMD composition
    inside the body is what's lost, not correctness)."""
    import jax as _jax
    try:
        major, minor = _jax.__version_info__[:2]
    except Exception:  # pragma: no cover
        return True
    return (major, minor) >= (0, 5)


def probe_residuals(stage_fn: Callable, chunk_avals, x_aval) -> Dict[str, Any]:
    """Abstractly trace one chunk's jax.vjp and report its residual
    layout: {"treedef", "param_pos" (per-leaf index into the chunk's
    param leaves, -1 = activation-derived), "buf_avals" (avals of the
    leaves that must ride buffers in store-activations mode)}.

    Single source of truth for both the store-mode engine and the
    memory-budget auto-pick — the two must agree on what gets buffered.
    Residual leaves that ARE param leaves (same tracer in this trace —
    jaxpr construction is deterministic, so positions are stable) are
    re-picked from live params at the backward tick instead of being
    buffered.
    """
    import jax

    out: Dict[str, Any] = {}

    def _probe(pj, x):
        res, vjp = jax.vjp(stage_fn, pj, x)
        leaves, td = jax.tree_util.tree_flatten(vjp)
        pleaves = jax.tree_util.tree_leaves(pj)
        pmap = {id(pl): k for k, pl in enumerate(pleaves)}
        out["treedef"] = td
        out["param_pos"] = [pmap.get(id(l), -1) for l in leaves]
        out["buf_avals"] = [
            jax.ShapeDtypeStruct(l.shape, l.dtype)
            for l, pos in zip(leaves, out["param_pos"]) if pos < 0]
        return res

    jax.eval_shape(_probe, chunk_avals, x_aval)
    return out


def pipeline_forward_backward(stage_fn: Callable, loss_fn: Callable,
                              stacked_params, loss_params,
                              x_microbatches, y_microbatches,
                              mesh, sched: PipelineSchedule,
                              axis: str = "pp", remat: bool = True):
    """Run one pipelined train micro-step: forward + backward fused.

    stage_fn(chunk_params, x) -> y      one chunk's computation; uniform
                                        activation shape across chunks.
    loss_fn(loss_params, y, target) -> scalar mean loss per microbatch.
    stacked_params: pytree, leaves [vpp, n_stages, ...] (dim 1 sharded
        over `axis`; dim 0 is the chunk round).
    x_microbatches / y_microbatches: [n_micro, ...].

    remat=True (the 1F1B memory story): backward re-runs the chunk
    forward from its saved input — O(act_buf_size) inputs held, +1 fwd
    of compute per tick. remat=False (store-activations, the reference
    default — pipeline_parallel.py:440 stores, it doesn't remat): the
    forward slot runs jax.vjp and its residuals ride buffers to the
    backward tick; param-only residual leaves are substituted from the
    live params at backward instead of being buffered, so params are
    never duplicated per slot.

    Returns (loss, grads_stacked, grads_loss_params, dxs) where loss is
    the mean over microbatches, grads are summed cotangents (d mean-loss),
    and dxs [n_micro, ...] is the gradient w.r.t. x_microbatches (for an
    embedding stage living outside the pipeline).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    jmesh = _resolve_mesh(mesh)
    p, v, m = sched.n_stages, sched.vpp, sched.n_micro
    is_zb = sched.mode == "zb"
    if is_zb and remat:
        raise ValueError(
            "zero-bubble schedules require store-activations mode "
            "(remat=False): the B and W slots share stored vjp "
            "residuals; remat would re-run each chunk forward twice")
    if jmesh.shape[axis] != p:
        raise ValueError(f"mesh axis {axis!r} has size {jmesh.shape[axis]}, "
                         f"schedule built for {p} stages")
    if x_microbatches.shape[0] != m:
        raise ValueError(f"got {x_microbatches.shape[0]} microbatches, "
                         f"schedule built for {m}")
    for leaf in jax.tree_util.tree_leaves(stacked_params):
        if leaf.shape[:2] != (v, p):
            raise ValueError(
                f"stacked_params leaves must be [vpp={v}, n_stages={p}, "
                f"...]; got {leaf.shape}")

    tables = {k: jnp.asarray(a) for k, a in sched.tables.items()}
    inv_m = 1.0 / float(m)

    param_specs = jax.tree_util.tree_map(lambda _: P(None, axis),
                                         stacked_params)
    ring_fwd = [(i, (i + 1) % p) for i in range(p)]
    ring_bwd = [(i, (i - 1) % p) for i in range(p)]

    def body(params, lparams, xs, ys, stage_ids):
        p_local = jax.tree_util.tree_map(lambda a: a[:, 0], params)
        # stage id arrives as a P(axis)-sharded arange instead of
        # jax.lax.axis_index: on jax<=0.4.x axis_index inside a
        # partially-manual shard_map lowers to a PartitionId HLO that
        # the SPMD partitioner rejects whenever the mesh has auto axes
        stage = stage_ids[0]

        chunk0 = jax.tree_util.tree_map(lambda a: a[0], p_local)
        a_shape = jax.eval_shape(stage_fn, chunk0, xs[0])
        if a_shape.shape != xs.shape[1:] or a_shape.dtype != xs.dtype:
            raise ValueError(
                f"pipeline chunks must preserve activation shape/dtype; "
                f"chunk maps {xs.shape[1:]}/{xs.dtype} -> "
                f"{a_shape.shape}/{a_shape.dtype}")
        act_z = jnp.zeros(a_shape.shape, a_shape.dtype)

        def pick_chunk(tree, j):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, False), tree)

        # --- store-activations support: the shared residual-layout
        # probe (see probe_residuals) tells which vjp residual leaves
        # ride buffers vs get re-picked from live params at backward.
        res_probe: Dict[str, Any] = {}
        if not remat:
            res_probe = probe_residuals(stage_fn, chunk0, act_z)

        def _store_res(res_buf, vjp, slot):
            leaves = jax.tree_util.tree_leaves(vjp)
            buffered = [l for l, pos in zip(leaves,
                                            res_probe["param_pos"])
                        if pos < 0]
            return tuple(
                rb.at[slot].set(lf.astype(rb.dtype))
                for rb, lf in zip(res_buf, buffered))

        def _load_vjp(res_buf, slot, pj):
            pleaves = jax.tree_util.tree_leaves(pj)
            it = iter(res_buf)
            leaves = [pleaves[pos] if pos >= 0 else next(it)[slot]
                      for pos in res_probe["param_pos"]]
            return jax.tree_util.tree_unflatten(res_probe["treedef"],
                                                leaves)

        def loss_and_seeds(out, y):
            (lv, (g_lp, g_out)) = jax.value_and_grad(
                lambda lp, o: loss_fn(lp, o, y), argnums=(0, 1))(lparams, out)
            return lv, g_out, g_lp

        zero_lp = jax.tree_util.tree_map(jnp.zeros_like, lparams)

        def tick(carry, row):
            (fwd_msg, bwd_msg, act_buf, grad_buf, res_buf, gacc, lp_acc,
             loss_sum, dxs) = carry
            r = {k: a[stage] for k, a in row.items()}

            # -- message arrivals (written before compute reads) --
            incoming = jax.lax.ppermute(fwd_msg, axis, ring_fwd)
            g_incoming = jax.lax.ppermute(bwd_msg, axis, ring_bwd)
            act_buf = act_buf.at[r["rx_slot"]].set(
                jnp.where(r["rx_valid"], incoming, act_buf[r["rx_slot"]]))
            grad_buf = grad_buf.at[r["grx_slot"]].set(
                jnp.where(r["grx_valid"], g_incoming,
                          grad_buf[r["grx_slot"]]))

            # -- forward slot (cond-skipped: a stage with no fwd work
            # this tick pays nothing — warmup/cooldown ticks no longer
            # burn a full masked chunk-forward) --
            x_in = jnp.where(r["fwd_is_first"], xs[r["fwd_mb"]],
                             act_buf[r["fwd_in_slot"]])
            pj_f = pick_chunk(p_local, r["fwd_chunk"])

            def fwd_do(x_in, res_buf):
                if remat:
                    return stage_fn(pj_f, x_in), res_buf
                out, vjp_f = jax.vjp(stage_fn, pj_f, x_in)
                return out, _store_res(res_buf, vjp_f, r["res_slot"])

            out, res_buf = jax.lax.cond(
                r["fwd_valid"], fwd_do,
                lambda x_in, res_buf: (act_z, res_buf), x_in, res_buf)
            lv, g_seed, g_lp = jax.lax.cond(
                r["fwd_is_last"],
                lambda o: loss_and_seeds(o, ys[r["fwd_mb"]]),
                lambda o: (jnp.zeros((), jnp.float32),
                           jnp.zeros_like(o), zero_lp),
                out)
            last_valid = jnp.logical_and(r["fwd_valid"], r["fwd_is_last"])
            grad_buf = grad_buf.at[r["fwd_seed_slot"]].set(
                jnp.where(last_valid, g_seed.astype(grad_buf.dtype),
                          grad_buf[r["fwd_seed_slot"]]))
            loss_sum = loss_sum + jnp.where(last_valid,
                                            lv.astype(jnp.float32), 0.0)
            lp_acc = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(last_valid, g, 0.0).astype(a.dtype),
                lp_acc, g_lp)

            # -- backward slot (cond-skipped like the forward) --
            pj = pick_chunk(p_local, r["bwd_chunk"])
            g_in = grad_buf[r["bwd_gslot"]]

            if is_zb:
                # zero-bubble: the backward slot computes ONLY the
                # input gradient (the critical-path B item); XLA DCEs
                # the unused dw matmuls out of the vjp call. The
                # weight gradient runs in the separate W slot below,
                # re-deriving the vjp from the same stored residuals.
                def bwdx_do(g_in, res_buf):
                    vjp = _load_vjp(res_buf, r["bwd_res_slot"], pj)
                    _, dx = vjp(g_in)
                    return dx

                dx = jax.lax.cond(
                    r["bwd_valid"], bwdx_do,
                    lambda g_in, res_buf: act_z, g_in, res_buf)
                pj_w = pick_chunk(p_local, r["w_chunk"])

                def w_do(res_buf, grad_buf):
                    vjp_w = _load_vjp(res_buf, r["w_res_slot"], pj_w)
                    dpw, _ = vjp_w(grad_buf[r["w_gslot"]])  # dx DCE'd
                    return dpw

                dp_w = jax.lax.cond(
                    r["w_valid"], w_do,
                    lambda res_buf, grad_buf: jax.tree_util.tree_map(
                        jnp.zeros_like, pj_w), res_buf, grad_buf)
                gacc = jax.tree_util.tree_map(
                    lambda acc, g: acc.at[r["w_chunk"]].add(
                        g.astype(acc.dtype)), gacc, dp_w)
            else:
                def bwd_do(g_in, act_buf, res_buf):
                    if remat:
                        # remat from the saved chunk input
                        xb = jnp.where(r["bwd_is_first"],
                                       xs[r["bwd_mb"]],
                                       act_buf[r["bwd_in_slot"]])
                        _, vjp = jax.vjp(stage_fn, pj, xb)
                    else:
                        # stored residuals (param leaves re-picked live)
                        vjp = _load_vjp(res_buf, r["bwd_res_slot"], pj)
                    return vjp(g_in)

                dp, dx = jax.lax.cond(
                    r["bwd_valid"], bwd_do,
                    lambda g_in, act_buf, res_buf: (
                        jax.tree_util.tree_map(jnp.zeros_like, pj),
                        act_z),
                    g_in, act_buf, res_buf)
                gacc = jax.tree_util.tree_map(
                    lambda acc, g: acc.at[r["bwd_chunk"]].add(
                        g.astype(acc.dtype)), gacc, dp)
            first_valid = jnp.logical_and(r["bwd_valid"], r["bwd_is_first"])
            dxs = dxs.at[r["bwd_mb"]].set(
                jnp.where(first_valid, dx.astype(dxs.dtype),
                          dxs[r["bwd_mb"]]))

            return (out, dx, act_buf, grad_buf, res_buf, gacc, lp_acc,
                    loss_sum, dxs), None

        res_buf0 = ()
        if not remat:
            res_buf0 = tuple(
                jnp.zeros((sched.res_buf_size,) + av.shape, av.dtype)
                for av in res_probe["buf_avals"])
        carry0 = (
            act_z, act_z,
            jnp.zeros((sched.act_buf_size,) + act_z.shape, act_z.dtype),
            jnp.zeros((sched.grad_buf_size,) + act_z.shape, act_z.dtype),
            res_buf0,
            jax.tree_util.tree_map(jnp.zeros_like, p_local),
            zero_lp,
            jnp.zeros((), jnp.float32),
            jnp.zeros((m,) + act_z.shape, act_z.dtype),
        )
        carry, _ = jax.lax.scan(tick, carry0, tables)
        (_, _, _, _, _, gacc, lp_acc, loss_sum, dxs) = carry

        # loss / loss-param grads / dxs live on one stage — broadcast.
        loss = jax.lax.psum(loss_sum, axis) * inv_m
        lp_grads = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, axis) * inv_m, lp_acc)
        dxs = jax.lax.psum(dxs, axis) * inv_m
        # stacked grads stay stage-local: reinsert the sharded stage dim.
        gacc = jax.tree_util.tree_map(lambda a: (a * inv_m)[:, None], gacc)
        return loss, gacc, lp_grads, dxs

    # partial-manual (auto axes compose via GSPMD) where the toolchain
    # supports it; fully-manual otherwise — see partial_manual_ok
    manual_kw = {"axis_names": {axis}} if partial_manual_ok() else {}
    f = jax.shard_map(
        body, mesh=jmesh,
        in_specs=(param_specs, P(), P(), P(), P(axis)),
        out_specs=(P(), param_specs, P(), P()),
        check_vma=False, **manual_kw)
    return f(stacked_params, loss_params, x_microbatches, y_microbatches,
             jnp.arange(p, dtype=jnp.int32))


def make_pipeline_loss_fn(stage_fn: Callable, loss_fn: Callable, mesh,
                          sched: PipelineSchedule, axis: str = "pp",
                          remat: bool = True):
    """Wrap the fused engine as a scalar-loss function differentiable by
    outer jax.grad: f(stacked_params, loss_params, xs, ys) -> loss.

    The engine already computes the exact gradients in its single fused
    pass; the custom_vjp just replays them scaled by the cotangent. This
    lets an embedding (or any pre-pipeline stage) live outside the
    pipeline and receive d loss/d xs through normal autodiff.
    """
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def pipeline_loss(stacked_params, loss_params, xs, ys):
        loss, _, _, _ = pipeline_forward_backward(
            stage_fn, loss_fn, stacked_params, loss_params, xs, ys,
            mesh, sched, axis, remat=remat)
        return loss

    def fwd(stacked_params, loss_params, xs, ys):
        loss, gs, glp, dxs = pipeline_forward_backward(
            stage_fn, loss_fn, stacked_params, loss_params, xs, ys,
            mesh, sched, axis, remat=remat)
        return loss, (gs, glp, dxs, ys)

    def bwd(res, gbar):
        gs, glp, dxs, ys = res
        scale = lambda t: jax.tree_util.tree_map(
            lambda a: (a.astype(jnp.float32) * gbar).astype(a.dtype), t)
        y_ct = jax.tree_util.tree_map(
            lambda y: np.zeros(y.shape, jax.dtypes.float0)
            if not jnp.issubdtype(y.dtype, jnp.inexact)
            else jnp.zeros_like(y), ys)
        return scale(gs), scale(glp), scale(dxs), y_ct

    pipeline_loss.defvjp(fwd, bwd)
    return pipeline_loss
