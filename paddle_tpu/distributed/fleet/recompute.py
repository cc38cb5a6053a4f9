"""Activation recompute (parity:
/root/reference/python/paddle/distributed/fleet/recompute/recompute.py:108).

TPU-native: jax.checkpoint IS the recompute engine — the reference's
RecomputeFunction PyLayer (save inputs, re-run forward in backward, RNG
state juggling via mp RNG tracker) collapses into one rematerialization
annotation that XLA schedules optimally. RNG correctness under remat is
handled by jax.checkpoint's deterministic key threading (our dropout draws
from fold_in counters, which replay identically).
"""
from __future__ import annotations

from typing import Any

import jax

from ...framework.core import Tensor, apply, no_grad
from ...jit import _SwapGuard, _unwrap_tree
from ...utils import telemetry

__all__ = ["recompute", "recompute_sequential"]


class _SubFn:
    """Generic recompute() adapter over a named sub-block of a layer:
    _SubFn(layer, "method", (modules...)) rematerializes
    layer.method(x), exposing the modules' parameters for the swap.
    Model families share this instead of growing bespoke adapters."""

    def __init__(self, layer, method, modules):
        self.layer = layer
        self.method = method
        self.modules = modules

    def parameters(self):
        ps = []
        for m in self.modules:
            ps.extend(m.parameters())
        return ps

    def __call__(self, x):
        return getattr(self.layer, self.method)(x)


def recompute(function, *args, keep=(), use_reentrant: bool = True,
              **kwargs):
    """Run function(*args) with activation rematerialization in backward.

    ``keep``: names (``jax.ad_checkpoint.checkpoint_name``) of values made
    inside ``function`` that are kept for the backward pass and not made
    again; everything else is. The default keeps nothing but the region's
    inputs, the most memory a region can save. A kernel whose output costs
    more to remake than to hold names it (the flash kernels:
    ``ops.pallas.flash_attention.FLASH_KEEP``) and the caller whose step
    has the room asks for it; a name nothing inside carries keeps
    nothing."""
    preserve = kwargs.pop("preserve_rng_state", True)
    metrics = telemetry.default_tracer().metrics
    metrics.inc("recompute.regions")
    if keep:
        metrics.inc("recompute.regions_keeping")
    layer_params = []
    if hasattr(function, "parameters"):
        layer_params = [p for p in function.parameters()]
    tensor_args = [a for a in args if isinstance(a, Tensor)]
    tensor_pos = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    n_p = len(layer_params)

    treedef_holder = {}

    def pure(*arrs):
        p_arrs = arrs[:n_p]
        i_arrs = arrs[n_p:]
        full_args = list(args)
        for pos, a in zip(tensor_pos, i_arrs):
            full_args[pos] = Tensor(a)
        with _SwapGuard(layer_params, list(p_arrs)):
            with no_grad():
                out = function(*full_args, **kwargs)
        flat, treedef = jax.tree_util.tree_flatten(_unwrap_tree(out))
        treedef_holder["treedef"] = treedef
        return tuple(flat) if len(flat) > 1 else flat[0]

    if keep:
        ckpt = jax.checkpoint(
            pure, policy=jax.checkpoint_policies.save_only_these_names(*keep))
    else:
        ckpt = jax.checkpoint(pure)
    result = apply("recompute", ckpt, *layer_params, *tensor_args)
    flat = list(result) if isinstance(result, tuple) else [result]
    return jax.tree_util.tree_unflatten(treedef_holder["treedef"], flat)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """paddle recompute_sequential parity: chunked recompute over a
    Sequential container."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    layers = list(functions)
    n = len(layers)
    per = max(1, n // segments)
    out = args
    i = 0
    while i < n:
        chunk = layers[i:i + per]

        def run_chunk(*xs, _chunk=tuple(chunk)):
            y = xs if len(xs) > 1 else xs[0]
            for l in _chunk:
                y = l(y) if not isinstance(y, tuple) else l(*y)
            return y

        class _ChunkFn:
            def __init__(self, chunk):
                self.chunk = chunk

            def parameters(self):
                ps = []
                for l in self.chunk:
                    ps.extend(l.parameters())
                return ps

            def __call__(self, *xs):
                y = xs if len(xs) > 1 else xs[0]
                for l in self.chunk:
                    y = l(y) if not isinstance(y, tuple) else l(*y)
                return y

        out = recompute(_ChunkFn(chunk), *(out if isinstance(out, tuple)
                                           else (out,)), **kwargs)
        out = out if isinstance(out, tuple) else (out,)
        i += per
    return out[0] if len(out) == 1 else out
