"""MoELayer (parity:
/root/reference/python/paddle/incubate/distributed/models/moe/moe_layer.py:263
plus gates gshard/switch/naive). Expert parallelism = sharding the expert
dim of the dispatched batch over the 'ep' (or 'mp') mesh axis — GSPMD
emits the token all-to-all the reference does manually with
global_scatter/global_gather."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply
from .. import functional as F
from .. import initializer as I
from .layers import Layer

__all__ = ["MoELayer", "MoEShareLayer", "SwitchGate", "GShardGate"]


class _GateBase:
    top_k = 2


class GShardGate(_GateBase):
    def __init__(self, top_k=2):
        self.top_k = top_k


class SwitchGate(_GateBase):
    top_k = 1


class MoELayer(Layer):
    """Token-routed expert FFN block that holds every expert.

    Args mirror the reference MoELayer where sensible. Each expert is the
    plain two-matrix FFN ``w2(act(w1 x))`` (no gate matrix; ``activation``
    is gelu, relu or silu), stored stacked [E, ...] so the expert dim can
    shard over the mesh. The gated three-matrix expert
    ``w_down(silu(w_gate x) * w_up x)``, and a layer that holds only its
    share of the experts, is ``MoEShareLayer``.
    """

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 gate="gshard", top_k: int = 2,
                 capacity_factor: float = 1.25, activation="gelu",
                 ep_axis: str = "ep", name=None,
                 dispatch_mode: str = "dense"):
        super().__init__()
        if dispatch_mode not in ("dense", "ragged"):
            raise ValueError(
                f"dispatch_mode must be 'dense' (GShard one-hot, "
                f"EP-shardable) or 'ragged' (sort-based dropless, the "
                f"large-E on-chip path); got {dispatch_mode!r}")
        self.dispatch_mode = dispatch_mode
        self.d_model = d_model
        self.d_hidden = d_hidden
        self.num_experts = num_experts
        if isinstance(gate, SwitchGate):
            self.top_k = 1
        elif isinstance(gate, _GateBase):
            self.top_k = gate.top_k
        elif gate == "switch":
            self.top_k = 1
        else:
            self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.ep_axis = ep_axis
        self._act_name = activation
        self.gate_weight = self.create_parameter(
            (d_model, num_experts), default_initializer=I.XavierUniform())
        self.w1 = self.create_parameter(
            (num_experts, d_model, d_hidden),
            default_initializer=I.XavierUniform())
        self.w2 = self.create_parameter(
            (num_experts, d_hidden, d_model),
            default_initializer=I.XavierUniform())
        self._aux_loss: Optional[Tensor] = None
        self._annotate_ep()

    def _annotate_ep(self):
        """Shard expert-stacked params over the ep axis when a fleet mesh
        with that axis exists."""
        from ...distributed.fleet import get_hybrid_communicate_group
        hcg = get_hybrid_communicate_group()
        if hcg is None:
            self._mesh = None
            return
        mesh = hcg.mesh
        if self.ep_axis not in mesh.dim_names or \
                mesh.get_dim_size(self.ep_axis) <= 1:
            # fall back to the mp axis for expert sharding
            self.ep_axis = "mp" if mesh.get_dim_size("mp") > 1 else None
        self._mesh = mesh
        if self.ep_axis is None:
            return
        from ...distributed.placement import Replicate, Shard
        from ...distributed.fleet.mpu import _annotate_param
        for p in (self.w1, self.w2):
            _annotate_param(p, mesh, 0, self.ep_axis)

    def _ep_sharding(self):
        if self._mesh is None or self.ep_axis is None:
            return None
        spec = [self.ep_axis, None, None]
        return jax.sharding.NamedSharding(
            self._mesh.to_jax_mesh(), jax.sharding.PartitionSpec(*spec))

    def forward(self, x):
        from ...ops.moe import moe_dispatch_combine, moe_ragged_forward
        act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
               "silu": jax.nn.silu}[self._act_name]
        ep_sharding = self._ep_sharding()
        ragged = self.dispatch_mode == "ragged"
        if ragged and ep_sharding is not None:
            raise NotImplementedError(
                "dispatch_mode='ragged' cannot shard over an expert-"
                "parallel mesh axis (segment sizes are data-dependent); "
                "use dispatch_mode='dense' under EP, or give each rank a "
                "MoEShareLayer(share=(rank, ranks)), which is dropless "
                "over the experts it is told it holds")

        def f(xa, gw, w1, w2):
            if ragged:
                out, aux, stats = moe_ragged_forward(
                    xa, gw, w1, w2, self.top_k, act)
                cap = jnp.float32(0.0)       # dropless: no capacity
            else:
                out, aux, stats = moe_dispatch_combine(
                    xa, gw, w1, w2, self.top_k, self.capacity_factor,
                    act, ep_sharding)
                cap = stats["capacity"]
            return (out, aux, stats["tokens_per_expert"],
                    stats["assigned_per_expert"],
                    stats["dropped_fraction"], cap)

        out, aux, routed, assigned, dropped, cap = apply(
            "moe", f, x, self.gate_weight, self.w1, self.w2)
        self._aux_loss = aux
        if isinstance(routed._value, jax.core.Tracer):
            # inside a compiled program the stats are traced values that
            # must not leak out of the trace; None (not stale numbers)
            self._last_stats = None
        else:
            self._last_stats = {
                "tokens_per_expert": routed,
                "assigned_per_expert": assigned,
                "dropped_fraction": dropped,
                "capacity": cap,
            }
        return out

    @property
    def aux_loss(self) -> Optional[Tensor]:
        """Load-balancing loss of the last forward (add to the train loss)."""
        return self._aux_loss

    @property
    def routing_stats(self) -> Optional[dict]:
        """Expert-utilization / capacity-overflow diagnostics of the last
        EAGER forward (reference surfaces these through the moe utils
        counters): tokens_per_expert, assigned_per_expert,
        dropped_fraction, capacity — Tensors, fetch with .numpy().
        None when the last forward ran inside a compiled program (run
        one eager forward to sample routing)."""
        return getattr(self, "_last_stats", None)


class MoEShareLayer(Layer):
    """One share of a gated-expert layer: it is told which experts it
    holds, routes over all of them and computes its own experts' part.

    ``share=(index, count)`` divides the ``num_experts`` experts evenly
    over ``count`` holders; this layer has the parameters of experts
    ``index * num_experts // count`` onward, ``num_experts // count`` of
    them, each ``w_down(act(w_gate x) * w_up x)``: ``activation`` is the
    gate's, ``"silu"`` (SwiGLU: Keye-VL-2.0, LFM2) or ``"relu"`` (ReGLU:
    SmallThinker). The router
    (``gate_weight`` [d_model, num_experts]) is whole on every share and
    follows one of two published rules. ``score_func="softmax"``
    (Keye-VL-2.0, SmallThinker, Laguna, Qwen3-MoE): softmax in float32,
    the ``top_k`` largest,
    divided by their sum when ``norm_topk_prob``, times
    ``routed_scaling_factor``. ``"sigmoid"`` (LFM2-MoE,
    DeepSeek-V3): ``s = sigmoid(logits)`` in float32; with
    ``expert_bias=True`` the ``top_k`` largest of ``s + expert_bias`` are
    chosen, ``expert_bias`` [num_experts] a float32 buffer that starts at
    zero and takes no gradient (what balances the load writes it; nothing
    here does); the weights are ``s`` at the chosen experts over their sum
    + 1e-6 when ``norm_topk_prob``, times ``routed_scaling_factor``.
    ``forward`` returns the sum over a token's
    chosen experts that are held here; what the other shares hold is
    theirs to add, which under expert parallelism is the exchange and on
    a single share is left out. ``share=(0, 1)`` is the whole layer. No
    token is dropped (``ops.moe.moe_share_forward``).

    ``forward(x, router_input=None)``: the experts always read ``x``; the
    router's logits come from ``router_input`` [batch, seq, d_model] where
    it is given (SmallThinker routes on the layer's input, before
    attention, and feeds the experts the stream after it), else from
    ``x``. The router's gradient flows to the tensor it read.

    ``rows`` is a buffer of ``num_held + 2`` counters that every forward
    adds to: the rows each held expert computed, then the rows the
    layer's gathers and scatter-adds walked to reach them (whole blocks
    of the sorted rows up to the last held one, so a little more than the
    held rows and, in a chunk sized for twice an even share, about half
    the chunk), then all the (token, choice) rows routed anywhere. A
    counter is two int32 words, the low
    30 bits and the carries out of them (at 2 x 8192 tokens and top-8 one
    word would wrap after 16,384 steps); a call adds fewer than 2**30
    rows. ``jit.TrainStep`` threads the buffer through the compiled step
    like any other; ``routing_counts()`` reads it.
    """
    _LOW_BITS = 30

    def __init__(self, d_model: int, d_hidden: int, num_experts: int,
                 top_k: int, share=(0, 1), norm_topk_prob: bool = True,
                 dtype=None, score_func: str = "softmax",
                 expert_bias: bool = False,
                 routed_scaling_factor: float = 1.0,
                 activation: str = "silu"):
        super().__init__(dtype=dtype)
        index, count = share
        if num_experts % count or not 0 <= index < count:
            raise ValueError(f"share {share!r} does not divide "
                             f"{num_experts} experts evenly")
        if score_func not in ("softmax", "sigmoid"):
            raise ValueError(f"score_func {score_func!r}; there are: "
                             "softmax, sigmoid")
        if score_func == "softmax" and expert_bias:
            raise ValueError("the softmax rule has no selection bias")
        from ...ops.moe import ACTIVATIONS
        if activation not in ACTIVATIONS:
            raise ValueError(f"activation {activation!r}; there are: "
                             f"{', '.join(ACTIVATIONS)}")
        self.score_func = score_func
        self.activation = activation
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.num_experts, self.top_k = num_experts, top_k
        self.num_held = num_experts // count
        self.first_expert = index * self.num_held
        self.norm_topk_prob = norm_topk_prob
        self.gate_weight = self.create_parameter((d_model, num_experts))
        self.w_gate = self.create_parameter(
            (self.num_held, d_model, d_hidden))
        self.w_up = self.create_parameter((self.num_held, d_model, d_hidden))
        self.w_down = self.create_parameter(
            (self.num_held, d_hidden, d_model))
        self.register_buffer(
            "rows", Tensor(jnp.zeros((2, self.num_held + 2), jnp.int32)))
        if expert_bias:
            self.register_buffer(
                "expert_bias", Tensor(jnp.zeros((num_experts,), jnp.float32)))
        else:
            self.expert_bias = None

    def compute(self, x, router_input=None):
        """(out, the counters this call adds) with the buffer untouched:
        for a caller that runs the layer inside a rematerialised region
        and counts outside it (``count``)."""
        from ...ops import moe
        from ...utils import telemetry
        metrics = telemetry.default_tracer().metrics
        metrics.inc("moe.dispatch.share_ragged")
        if self.expert_bias is not None:
            metrics.inc("moe.route.sigmoid_bias")
        if self.activation == "relu":
            metrics.inc("moe.expert.relu")
        routed = x.shape[0] * x.shape[1] * self.top_k
        has_bias = self.expert_bias is not None

        def f(xa, gw, wg, wu, wd, *more):
            more = list(more)
            bias = more.pop(0) if has_bias else None
            routed_on = more.pop(0) if more else None
            route = functools.partial(
                moe.route_softmax, scaling=self.routed_scaling_factor) \
                if self.score_func == "softmax" else functools.partial(
                    moe.route_sigmoid, expert_bias=bias,
                    scaling=self.routed_scaling_factor)
            out, rows, walked = moe.moe_share_forward(
                xa, gw, wg, wu, wd, self.top_k, self.first_expert,
                self.norm_topk_prob, route, self.activation, routed_on)
            return out, jnp.concatenate(
                [rows, walked[None], jnp.full((1,), routed, jnp.int32)])

        more = ((self.expert_bias,) if has_bias else ()) \
            + (() if router_input is None else (router_input,))
        return apply("moe_share", f, x, self.gate_weight, self.w_gate,
                     self.w_up, self.w_down, *more)

    def count(self, seen):
        low, high = self.rows._value
        low = low + seen._value
        self.rows._replace(jnp.stack(
            [low & ((1 << self._LOW_BITS) - 1),
             high + (low >> self._LOW_BITS)]))

    def forward(self, x, router_input=None):
        out, seen = self.compute(x, router_input)
        self.count(seen)
        return out

    def routing_counts(self) -> dict:
        """{"rows_held", "rows_max_expert", "rows_walked", "rows_routed"}
        since the layer was built (a read of the buffer: it waits for the
        device)."""
        import numpy as np
        low, high = np.asarray(self.rows._value).tolist()
        *held, walked, routed = [lo + (hi << self._LOW_BITS)
                                 for lo, hi in zip(low, high)]
        return {"rows_held": sum(held), "rows_max_expert": max(held),
                "rows_walked": walked, "rows_routed": routed}

    @staticmethod
    def summed_counts(layers) -> dict:
        """``routing_counts`` of a model's expert layers together
        (``rows_max_expert``: the busiest single expert of any layer)."""
        counts = [layer.routing_counts() for layer in layers]
        return {name: (max if name == "rows_max_expert" else sum)(
            c[name] for c in counts) for name in counts[0]}
