"""paddle_tpu.jit — to_static / compiled train steps.

Replaces the reference's entire graph-capture stack — dy2static AST
transforms (/root/reference/python/paddle/jit/dy2static/), the SOT bytecode
JIT (/root/reference/python/paddle/jit/sot/) and its C eval-frame hook
(/root/reference/paddle/fluid/pybind/eval_frame.c) — with jax.jit tracing:
the eager Tensor ops run unchanged on tracers, so "graph capture" is just
calling the model inside a trace. Guards (SOT's retrace conditions) become
XLA's shape/dtype cache keys.

Key pieces:
- ``functional_call``: run a Layer with swapped-in parameter/buffer arrays
  (torch.func-style), returning outputs + updated buffers. This is what
  makes the mutable Layer API compose with functional transforms.
- ``to_static``: paddle.jit.to_static parity. Compiled forward whose
  backward is a single taped VJP of the whole compiled graph.
- ``TrainStep``: whole-training-step compilation (fwd+bwd+optimizer) with
  buffer donation — the intended high-performance path on TPU.
"""
from __future__ import annotations

import functools
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import (
    Parameter, Tensor, apply, no_grad, with_rng_key, default_generator,
)
from ..utils import telemetry

__all__ = ["functional_call", "to_static", "TrainStep", "save", "load",
           "not_to_static", "ignore_module"]


# ---------------------------------------------------------------------------
# functional_call
# ---------------------------------------------------------------------------

def _collect(layer):
    params = list(layer.named_parameters())
    buffers = [(n, b) for n, b in layer.named_buffers() if b is not None]
    return params, buffers


class _SwapGuard:
    """Temporarily replace Tensor._value on params/buffers with provided
    (possibly traced) arrays; restore originals on exit and capture the
    post-call buffer values (BatchNorm running stats etc.)."""

    def __init__(self, tensors: List[Tensor], arrays: List[jax.Array]):
        self.tensors = tensors
        self.arrays = arrays
        self.saved = None

    def __enter__(self):
        self.saved = [t._value for t in self.tensors]
        for t, a in zip(self.tensors, self.arrays):
            t._value = a
        return self

    def read_current(self):
        return [t._value for t in self.tensors]

    def __exit__(self, *exc):
        for t, v in zip(self.tensors, self.saved):
            t._value = v
        return False


def _unwrap_tree(x):
    if isinstance(x, Tensor):
        return x._value
    if isinstance(x, (list, tuple)):
        return type(x)(_unwrap_tree(e) for e in x)
    if isinstance(x, dict):
        return {k: _unwrap_tree(v) for k, v in x.items()}
    return x


def _wrap_tree(x, stop_gradient=True):
    if isinstance(x, (jnp.ndarray, jax.Array)) or hasattr(x, "dtype"):
        return Tensor(x, stop_gradient=stop_gradient)
    if isinstance(x, (list, tuple)):
        return type(x)(_wrap_tree(e, stop_gradient) for e in x)
    if isinstance(x, dict):
        return {k: _wrap_tree(v, stop_gradient) for k, v in x.items()}
    return x


def _call_swapped(layer, param_arrays, buffer_arrays, args, kwargs, then):
    """``layer(*args)`` and then ``then(output_pytree_of_arrays)``, both
    with the given arrays in place of the layer's parameters and buffers:
    whatever ``then`` reads from the layer is what the caller passed in,
    not the live value. The buffers are read back between the two.
    Returns (what ``then`` returned, new_buffer_arrays)."""
    kwargs = kwargs or {}
    params, buffers = _collect(layer)
    p_tensors = [p for _, p in params]
    b_tensors = [b for _, b in buffers]
    targs = tuple(a if isinstance(a, Tensor) else Tensor(a) for a in args)
    with _SwapGuard(p_tensors, list(param_arrays)), \
         _SwapGuard(b_tensors, list(buffer_arrays)) as bguard:
        with no_grad():
            out = layer(*targs, **kwargs)
        new_buffers = bguard.read_current()
        result = then(_unwrap_tree(out))
    return result, new_buffers


def functional_call(layer, param_arrays: Sequence[jax.Array],
                    buffer_arrays: Sequence[jax.Array], args: tuple,
                    kwargs: Optional[dict] = None):
    """Run ``layer(*args)`` with parameters/buffers replaced by the given
    arrays. args are raw arrays or Tensors. Returns
    (output_pytree_of_arrays, new_buffer_arrays)."""
    return _call_swapped(layer, param_arrays, buffer_arrays, args, kwargs,
                         lambda out: out)


# ---------------------------------------------------------------------------
# to_static
# ---------------------------------------------------------------------------

_RETRACE_WARN_THRESHOLD = 8


def _trace_error(exc, fn_name):
    """Rewrap jax tracing failures with actionable paddle-level guidance
    (the SOT-guard analog: reference jit/sot/translate.py:31 falls back on
    graph breaks; here we say exactly what to change or offer
    full_graph=False eager fallback)."""
    import jax.errors as jerr
    msg = None
    if isinstance(exc, jerr.TracerBoolConversionError) or \
            "TracerBoolConversionError" in type(exc).__name__:
        msg = ("data-dependent Python control flow (if/while on a traced "
               "Tensor value). Use paddle_tpu.static.nn.cond / "
               "while_loop / switch_case, move the branch out of the "
               "compiled function, or pass full_graph=False to run this "
               "function eagerly")
    elif isinstance(exc, jerr.ConcretizationTypeError):
        msg = ("a traced Tensor was used where a concrete Python value is "
               "required (e.g. int(x), x.item(), shape-dependent Python "
               "logic). Hoist the value out of the compiled function or "
               "pass full_graph=False")
    elif isinstance(exc, jerr.TracerArrayConversionError):
        msg = ("a traced Tensor was converted to numpy (np.asarray/"
               ".numpy()) inside the compiled region. Keep the "
               "computation in paddle/jax ops, or pass full_graph=False")
    if msg is None:
        return None
    return RuntimeError(
        f"to_static({fn_name}): cannot compile — {msg}.\n"
        f"Original error: {type(exc).__name__}: {exc}")


def _prim() -> bool:
    from ..decomposition.register import prim_enabled
    return prim_enabled()


def _snapshot_lower(p_arrays, b_arrays, key, training, args):
    """Aval-only snapshot for concrete_program (live arrays would pin
    the batch + params in HBM)."""
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return ([sds(p) for p in p_arrays], [sds(b) for b in b_arrays],
            key, training,
            tuple(sds(a._value) if isinstance(a, Tensor) else a
                  for a in args))


class StaticFunction:
    """Compiled callable over a Layer or plain function of Tensors.

    Forward runs under jax.jit; backward through the result is ONE taped
    node whose VJP is the XLA-compiled cotangent program (the analog of the
    reference's whole-program backward in partial_program.py).

    Robustness (reference SOT parity, jit/sot/):
    - untraceable constructs raise actionable errors naming the fix;
    - full_graph=False falls back to EAGER execution when tracing fails
      (the graph-break analog: correctness first, speed when possible);
    - every retrace is counted and the triggering signature recorded
      (`retrace_count` / `trace_signatures`); crossing
      _RETRACE_WARN_THRESHOLD logs a cache-churn warning.
    """

    def __init__(self, fn_or_layer, input_spec=None, build_strategy=None,
                 full_graph=True):
        self._layer = fn_or_layer if hasattr(fn_or_layer, "forward") else None
        self._fn = fn_or_layer if self._layer is None else None
        self._compiled = None
        self._input_spec = input_spec
        self._full_graph = full_graph
        self._partial = None        # PartialProgram after a graph break
        self.retrace_count = 0
        self.trace_signatures = []

    def _note_trace(self, in_arrays):
        if getattr(self, "_suppress_note", False):
            return  # introspective lowering is not a retrace
        self.retrace_count += 1
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in in_arrays)
        self.trace_signatures.append(sig)
        if len(self.trace_signatures) > 16:   # telemetry, not a log
            del self.trace_signatures[:-16]
        if self.retrace_count == _RETRACE_WARN_THRESHOLD:
            import warnings
            warnings.warn(
                f"to_static({self._name()}) retraced "
                f"{self.retrace_count} times — every new input "
                f"shape/dtype compiles a new program. Recent signatures: "
                f"{self.trace_signatures[-4:]}. Pad inputs to fixed "
                f"shapes or bucket them.", RuntimeWarning)

    def _name(self):
        target = self._layer if self._layer is not None else self._fn
        return getattr(target, "__name__",
                       type(target).__name__ if target is not None else "?")

    # the pure array function
    def _build(self):
        layer = self._layer
        note = self._note_trace

        if layer is not None:
            # `mode` is the static cache token: (training, prim_enabled).
            # The prim flag only forces a retrace when toggled — the new
            # trace then reads the live flag through each DecompAware
            def pure(param_arrays, buffer_arrays, rng_key, mode, *in_arrays):
                note(in_arrays)
                training = mode[0] if isinstance(mode, tuple) else mode
                layer.training = training
                with with_rng_key(rng_key):
                    out, new_bufs = functional_call(
                        layer, param_arrays, buffer_arrays, in_arrays)
                return out, new_bufs
        else:
            fn = self._fn

            def pure(param_arrays, buffer_arrays, rng_key, mode, *in_arrays):
                note(in_arrays)
                targs = tuple(Tensor(a) for a in in_arrays)
                from ..framework.core import _watch_mutations
                with with_rng_key(rng_key), no_grad(), \
                        _watch_mutations() as (mutated, created):
                    out = fn(*targs)
                arg_ids = {id(t) for t in targs}
                leaked = [t for i, t in mutated.items()
                          if i not in created and i not in arg_ids]
                if leaked:
                    raise RuntimeError(
                        f"to_static({fn.__name__}): the function mutates "
                        f"{len(leaked)} Tensor(s) it does not own (buffer/"
                        f"global state writes). Tracing would silently "
                        f"drop these updates. Wrap the owning Layer with "
                        f"to_static instead (its buffers are threaded "
                        f"through the compiled program), or return the "
                        f"updated values explicitly.")
                return _unwrap_tree(out), []

        return jax.jit(pure, static_argnums=(3,))

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            # paddle.jit.enable_to_static(False): run the target eagerly
            target = self._layer if self._layer is not None else self._fn
            return target(*args, **kwargs)
        if self._partial is not None:
            return self._partial(*args, **kwargs)
        if self._compiled is None:
            self._compiled = self._build()
        try:
            return self._call_compiled(args, kwargs)
        except Exception as e:
            wrapped = _trace_error(e, self._name())
            if wrapped is None:
                raise
            if not self._full_graph:
                # graph break (SOT parity, reference jit/sot/translate.py):
                # compile the traceable segments, run the breaking
                # constructs eagerly between them
                return self._enter_partial(e, args, kwargs)
            raise wrapped from e

    def _enter_partial(self, cause, args, kwargs):
        import warnings
        from .partial_capture import PartialProgram
        # warn BEFORE executing anything: under warnings-as-errors this
        # must raise while state is still clean (no segments run)
        warnings.warn(
            f"to_static({self._name()}): whole-graph tracing failed "
            f"({type(cause).__name__}); switching to partial-graph "
            f"capture (compiled subgraphs around the breaking "
            f"constructs).", RuntimeWarning)
        target = (self._layer if self._layer is not None else self._fn)
        self._partial = PartialProgram(target, name=self._name())
        try:
            return self._partial(*args, **kwargs)
        except Exception:
            # Do NOT re-run eagerly: segments already executed with real
            # side effects (buffer updates, RNG draws) — a rerun would
            # double-apply them. Propagate; the next call retries
            # (whole-graph first, then partial) from clean state.
            self._partial = None
            raise

    # partial-capture telemetry (SOT parity surface)
    @property
    def graph_break_count(self):
        return self._partial.graph_break_count if self._partial else 0

    @property
    def num_subgraphs(self):
        return self._partial.num_subgraphs if self._partial else 0


    def _call_compiled(self, args, kwargs):
        if kwargs:
            raise NotImplementedError(
                f"to_static({self._name()}): keyword arguments "
                f"{sorted(kwargs)} are not supported by the compiled "
                "call signature — pass them positionally (silently "
                "running with defaults would be wrong)")
        layer = self._layer
        if layer is not None:
            params, buffers = _collect(layer)
            p_tensors = [p for _, p in params]
            b_tensors = [b for _, b in buffers]
            b_arrays = [b._value for b in b_tensors]
            key = default_generator.next_key()

            compiled = self._compiled
            training = layer.training
            n_params = len(p_tensors)

            # the per-call key rides as a positional arg, not a closure
            # cell: an outer capture context fingerprints closures by
            # cell content, so a captured fresh key would miss the
            # segment cache every call (FC203)
            def whole_graph(k, *arrs):
                pa = arrs[:n_params]
                ia = arrs[n_params:]
                out, new_bufs = compiled(list(pa), b_arrays, k,
                                         (training, _prim()), *ia)
                flat_out, treedef = jax.tree_util.tree_flatten(out)
                self._last_treedef = treedef
                self._last_n_out = len(flat_out)
                return tuple(flat_out) + tuple(new_bufs)

            results = apply("to_static", whole_graph, key, *p_tensors,
                            *args)
            if getattr(self, "_lower_trace_count", -1) != \
                    self.retrace_count:
                # aval-only snapshot for concrete_program, refreshed per
                # retrace (not per call): ShapeDtypeStructs, ALL args
                self._lower_args = _snapshot_lower(
                    [p._value for p in p_tensors], b_arrays, key,
                    (training, _prim()), args)
                self._lower_trace_count = self.retrace_count
            if not isinstance(results, tuple):
                results = (results,)
            n_out = self._last_n_out
            out_tensors = list(results[:n_out])
            new_buf_tensors = results[n_out:]
            for bt, nb in zip(b_tensors, new_buf_tensors):
                bt._replace(nb._value)
            out = jax.tree_util.tree_unflatten(
                self._last_treedef, out_tensors)
            return out
        # plain function
        key = default_generator.next_key()
        compiled = self._compiled

        def whole_graph(k, *arrs):
            out, _ = compiled([], [], k, (True, _prim()), *arrs)
            flat_out, treedef = jax.tree_util.tree_flatten(out)
            self._last_treedef = treedef
            return tuple(flat_out) if len(flat_out) > 1 else flat_out[0]

        results = apply("to_static", whole_graph, key, *args)
        if getattr(self, "_lower_trace_count", -1) != self.retrace_count:
            self._lower_args = _snapshot_lower([], [], key,
                                               (True, _prim()), args)
            self._lower_trace_count = self.retrace_count
        if isinstance(results, tuple):
            return jax.tree_util.tree_unflatten(self._last_treedef,
                                                list(results))
        return jax.tree_util.tree_unflatten(self._last_treedef, [results])

    # paddle API compat
    @property
    def forward(self):
        return self.__call__

    @property
    def concrete_program(self):
        """The traced program of the LAST call (reference
        ConcreteProgram, jit/dy2static/program_translator.py): inputs/
        outputs specs, parameters, and main_program — here the
        framework's IR is StableHLO, so main_program is the lowered
        StableHLO module text of the compiled forward."""
        if self._partial is not None:
            raise RuntimeError(
                "concrete_program: this function runs under PARTIAL "
                "graph capture (whole-graph tracing failed) — there is "
                "no single whole program to show; see num_subgraphs / "
                "graph_break_count for the capture telemetry")
        if self._compiled is None or \
                getattr(self, "_lower_args", None) is None:
            raise RuntimeError(
                "concrete_program: call the to_static function at least "
                "once (tracing is input-driven — shapes come from the "
                "first call)")
        return _ConcreteProgram(self)


class _ConcreteProgram:
    """Reference ConcreteProgram parity surface over the last trace:
    .inputs (specs), .parameters, .main_program — this framework's IR
    is StableHLO, so main_program is the lowered module text."""

    def __init__(self, static_fn: "StaticFunction"):
        self._sf = static_fn

    @property
    def inputs(self):
        # derived from the same snapshot main_program lowers — the two
        # views always describe the SAME program
        from ..static.program import InputSpec
        _, _, _, _, ia = self._sf._lower_args
        return [InputSpec(list(a.shape), a.dtype) for a in ia
                if hasattr(a, "shape")]

    @property
    def parameters(self):
        layer = self._sf._layer
        if layer is None:
            return []
        return [p for _, p in layer.named_parameters()]

    @property
    def main_program(self) -> str:
        sf = self._sf
        pa, ba, key, training, ia = sf._lower_args
        layer = sf._layer
        prev_training = getattr(layer, "training", None)
        sf._suppress_note = True     # tracing here is introspection,
        try:                         # not a retrace of the live model
            lowered = sf._compiled.lower(pa, ba, key, training, *ia)
        finally:
            sf._suppress_note = False
            if layer is not None and prev_training is not None:
                # pure() sets layer.training as a trace side effect —
                # introspection must not flip the live train/eval mode
                layer.training = prev_training
        return lowered.as_text()

    def __repr__(self):
        return (f"ConcreteProgram(inputs={self.inputs}, "
                f"n_params={len(self.parameters)}, ir=stablehlo)")


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True):
    """paddle.jit.to_static parity (/root/reference/python/paddle/jit/api.py:171).

    full_graph=False enables the graph-break analog: if tracing fails on
    an untraceable construct, the function runs eagerly instead (with a
    one-time warning) rather than erroring."""
    def decorate(fn):
        if hasattr(fn, "forward"):  # Layer: wrap call while keeping layer API
            static = StaticFunction(fn, input_spec, build_strategy,
                                    full_graph=full_graph)
            return _StaticLayerProxy(fn, static)
        return StaticFunction(fn, input_spec, build_strategy,
                              full_graph=full_graph)

    if function is not None:
        return decorate(function)
    return decorate


class _StaticLayerProxy:
    """Layer wrapper whose __call__ is compiled but which forwards
    everything else (state_dict, parameters, train/eval) to the layer.
    Reports the wrapped layer's __class__, so isinstance(proxy, Layer)
    (and isinstance against the concrete model class) hold; the layer
    instance itself is never mutated."""

    def __init__(self, layer, static_fn):
        object.__setattr__(self, "_layer", layer)
        object.__setattr__(self, "_static_fn", static_fn)

    @property
    def __class__(self):
        return type(self._layer)

    def __call__(self, *args, **kwargs):
        return self._static_fn(*args, **kwargs)

    # to_static telemetry/introspection lives on the StaticFunction
    _STATIC_ATTRS = frozenset({
        "concrete_program", "retrace_count", "trace_signatures",
        "graph_break_count", "num_subgraphs",
    })

    def __getattr__(self, name):
        if name in _StaticLayerProxy._STATIC_ATTRS:
            return getattr(self._static_fn, name)
        return getattr(self._layer, name)

    def __setattr__(self, name, value):
        setattr(self._layer, name, value)


def not_to_static(fn):
    return fn


def ignore_module(modules):
    return None


# ---------------------------------------------------------------------------
# TrainStep: whole-step compilation (the TPU fast path)
# ---------------------------------------------------------------------------

class TrainStep:
    """Compile forward+backward+optimizer into one XLA program.

    Usage:
        step = TrainStep(model, loss_fn, optimizer)   # loss_fn(out, *labels)
        loss = step(x, y)                             # Tensors in, loss out

    ``loss_fn`` runs while the step's traced arrays still stand in for
    the model's parameters and buffers, as the forward pass does: a
    weight it reads from the model (a chunked loss reads the head, a
    regulariser any weight) gets its gradient.

    The program is two stages: every trainable leaf's gradient is
    finished (``jax.lax.optimization_barrier`` over them all), then the
    optimizer runs. Without the boundary XLA fuses the optimizer's update
    into the weight-gradient matmuls and runs those after the whole
    backward pass, which slows the matmuls and keeps their operands alive
    to the program's end. Each trace of a step program adds the number
    of leaves under its barrier to ``train_step.grad_barrier_leaves`` in
    the default registry.

    The compiled program donates parameter/optimizer-state buffers, so
    updates are in-place in HBM (the analog of the reference interpreter's
    inplace pass + buffer GC, at zero runtime cost).
    """

    def __init__(self, model, loss_fn: Callable, optimizer,
                 donate: bool = True, mesh=None, in_shardings=None,
                 gradient_merge: int = 1, gradient_merge_avg: bool = True):
        self.model = model
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        params, buffers = _collect(model)
        self._param_names = [n for n, _ in params]
        self._p_tensors = [p for _, p in params]
        self._b_tensors = [b for _, b in buffers]
        # optimizer must own the same params (paddle-style construction)
        opt_ids = {id(p) for p in optimizer._parameter_list}
        if not all(id(p) in opt_ids for p in self._p_tensors
                   if not p.stop_gradient):
            raise ValueError("optimizer parameters must come from the model")
        self._trainable_mask = [not p.stop_gradient for p in self._p_tensors]
        self._compiled = None
        self._donate = donate
        self._step_i = 0
        # gradient merge (k-step accumulation; parity:
        # /root/reference/python/paddle/distributed/fleet/meta_optimizers/
        # gradient_merge_optimizer.py:21): accumulate k micro-step grads
        # in f32, apply the optimizer every k-th call
        self._gm_k = int(gradient_merge)
        if self._gm_k < 1:
            raise ValueError(f"gradient_merge must be >= 1, got {gradient_merge}")
        self._gm_avg = bool(gradient_merge_avg)
        self._gm_accum = None
        self._gm_compiled = None
        # spans go to this ring when one is set, else to the default ring
        # while a profiler session is live (utils/telemetry.py)
        self.tracer = None
        # the step program(s) register here when built: ``compiles`` and
        # ``seal()`` as on a ServingEngine
        self.compile_watch = telemetry.CompileWatch()

    def _make_loss_and_grads(self):
        """Closure computing (loss, new_buffers, per-param grads) — the
        shared forward+backward of both the plain and gradient-merge
        compiled programs. The gradients leave it under one
        optimization barrier: whatever consumes them (optimizer,
        accumulator) starts after the last of them is finished."""
        model = self.model
        loss_fn = self.loss_fn
        trainable_mask = self._trainable_mask

        def loss_and_grads(param_arrays, buffer_arrays, key, inputs, labels):
            train_params = [a for a, m in zip(param_arrays, trainable_mask)
                            if m]
            frozen = [a for a, m in zip(param_arrays, trainable_mask)
                      if not m]

            def loss_f(tp):
                it_t, it_f = iter(tp), iter(frozen)
                full = [next(it_t) if m else next(it_f)
                        for m in trainable_mask]

                # the loss runs under the forward pass's swap: a weight it
                # reads from the model (a chunked loss reads the head) is
                # the traced array and gets its gradient
                def loss_of(out):
                    with with_rng_key(jax.random.fold_in(key, 777)), \
                            no_grad():
                        out_t = _wrap_tree(out)
                        label_t = tuple(_wrap_tree(l) for l in labels)
                        loss_t = loss_fn(out_t, *label_t)
                    return loss_t._value.astype(jnp.float32)

                with with_rng_key(key):
                    return _call_swapped(model, full, buffer_arrays, inputs,
                                         None, loss_of)

            (loss, new_bufs), grads = jax.value_and_grad(
                loss_f, has_aux=True)(train_params)
            grads = jax.lax.optimization_barrier(grads)
            telemetry.default_tracer().metrics.inc(
                "train_step.grad_barrier_leaves", len(grads))
            # re-expand grads to the full param list (None for frozen)
            gi = iter(grads)
            full_grads = [next(gi) if m else None for m in trainable_mask]
            return loss, new_bufs, full_grads

        return loss_and_grads

    def _make_opt_update(self):
        """Closure applying the optimizer to full-per-param grads and
        pinning output placements (shared by both compiled programs)."""
        optimizer = self.optimizer

        def opt_update(param_arrays, full_grads, opt_state, lr):
            # align: optimizer params are a subset (usually ==) of model params
            id2idx = {id(p): i for i, p in enumerate(self._p_tensors)}
            opt_grads = [full_grads[id2idx[id(p)]] if id(p) in id2idx else None
                         for p in optimizer._parameter_list]
            opt_in = [param_arrays[id2idx[id(p)]]
                      for p in optimizer._parameter_list]
            new_opt_params, new_opt_state = optimizer.update(
                opt_in, opt_grads, opt_state, lr)
            # write updates back into the full param list
            new_params = list(param_arrays)
            for p, np_ in zip(optimizer._parameter_list, new_opt_params):
                if np_ is not None:
                    new_params[id2idx[id(p)]] = np_
            # pin outputs to their INPUT shardings: placements must be
            # STABLE across steps (otherwise e.g. ZeRO-1's sharded
            # optimizer update makes XLA emit sharded params, silently
            # drifting stage 1 into stage 3 after the first step; the
            # same applies to the optimizer states in reverse)
            new_params = [
                jax.lax.with_sharding_constraint(a, s)
                if s is not None else a
                for a, s in zip(new_params, self._param_shardings())]
            # `opt_state` here is a tracer: reading `.sharding` off it
            # raises on jax>=0.9, so the pin must come from the LIVE
            # concrete state captured at trace time (tracing happens on
            # the first __call__, after optimizer state init).
            opt_shardings = self._opt_state_shardings()
            new_leaves, new_td = jax.tree_util.tree_flatten(new_opt_state)
            old_leaves = jax.tree_util.tree_leaves(opt_state)
            if len(new_leaves) == len(old_leaves) == len(opt_shardings):
                pinned = [
                    jax.lax.with_sharding_constraint(new, s)
                    if (s is not None and hasattr(new, "shape")
                        and getattr(old, "shape", None) == new.shape)
                    else new
                    for new, old, s in zip(new_leaves, old_leaves,
                                           opt_shardings)]
                new_opt_state = jax.tree_util.tree_unflatten(new_td, pinned)
            elif any(s is not None for s in opt_shardings):
                # an optimizer whose update() changes the state's leaf
                # count would silently lose the ZeRO placement pin —
                # fail loudly instead of drifting sharded state
                raise ValueError(
                    "optimizer.update() returned a state tree whose leaf "
                    f"count ({len(new_leaves)}) differs from init_state's "
                    f"({len(opt_shardings)}); the sharded optimizer-state "
                    "placement pin cannot be applied. Keep the state "
                    "structure stable across steps.")
            return new_params, new_opt_state

        return opt_update

    def _build(self):
        loss_and_grads = self._make_loss_and_grads()
        opt_update = self._make_opt_update()

        def step(param_arrays, buffer_arrays, opt_state, lr, key, inputs,
                 labels):
            with jax.named_scope("fwd_bwd"):
                loss, new_bufs, full_grads = loss_and_grads(
                    param_arrays, buffer_arrays, key, inputs, labels)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = opt_update(
                    param_arrays, full_grads, opt_state, lr)
            return loss, new_params, new_bufs, new_opt_state

        donate = (0, 2) if self._donate else ()
        return jax.jit(step, donate_argnums=donate)

    def _build_gm(self):
        """Two compiled programs for gradient merge — an accumulate-only
        micro-step and an apply step — selected host-side by
        step_i % k (compile-static: no lax.cond over the optimizer)."""
        loss_and_grads = self._make_loss_and_grads()
        opt_update = self._make_opt_update()
        k, avg = self._gm_k, self._gm_avg
        mask = self._trainable_mask

        def accum_step(param_arrays, buffer_arrays, accum, key, inputs,
                       labels):
            with jax.named_scope("fwd_bwd"):
                loss, new_bufs, full_grads = loss_and_grads(
                    param_arrays, buffer_arrays, key, inputs, labels)
            tg = [g for g, m in zip(full_grads, mask) if m]
            new_accum = [a + g.astype(jnp.float32)
                         for a, g in zip(accum, tg)]
            return loss, new_bufs, new_accum

        def apply_step(param_arrays, buffer_arrays, opt_state, lr, accum,
                       key, inputs, labels):
            with jax.named_scope("fwd_bwd"):
                loss, new_bufs, full_grads = loss_and_grads(
                    param_arrays, buffer_arrays, key, inputs, labels)
            it = iter(accum)
            merged = []
            for g, m in zip(full_grads, mask):
                if not m:
                    merged.append(None)
                    continue
                tot = next(it) + g.astype(jnp.float32)
                if avg:
                    tot = tot / k
                # back to the native grad dtype so the optimizer update
                # behaves exactly like a plain step (keeps param dtype
                # stable for donation)
                merged.append(tot.astype(g.dtype))
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = opt_update(
                    param_arrays, merged, opt_state, lr)
            zero_accum = [jnp.zeros_like(a) for a in accum]
            return loss, new_params, new_bufs, new_opt_state, zero_accum

        da = (2,) if self._donate else ()
        db = (0, 2, 4) if self._donate else ()
        return (jax.jit(accum_step, donate_argnums=da),
                jax.jit(apply_step, donate_argnums=db))

    def _init_gm_accum(self):
        out = []
        for p, m in zip(self._p_tensors, self._trainable_mask):
            if not m:
                continue
            z = jnp.zeros(p._value.shape, jnp.float32)
            s = getattr(p._value, "sharding", None)
            if isinstance(s, jax.sharding.NamedSharding):
                z = jax.device_put(z, s)
            out.append(z)
        return out

    def _param_shardings(self):
        out = []
        for p in self._p_tensors:
            s = getattr(p._value, "sharding", None)
            out.append(s if isinstance(s, jax.sharding.NamedSharding)
                       else None)
        return out

    def _opt_state_shardings(self):
        """Concrete per-leaf NamedShardings of the live optimizer state
        (flattened order), None where unsharded/non-array."""
        out = []
        for leaf in jax.tree_util.tree_leaves(self.optimizer._state):
            s = getattr(leaf, "sharding", None)
            out.append(s if isinstance(s, jax.sharding.NamedSharding)
                       else None)
        return out

    def __call__(self, inputs, labels):
        """inputs / labels: a Tensor or tuple of Tensors. Model is called as
        model(*inputs); loss as loss_fn(model_out, *labels)."""
        with self._span("train_step", step=self._step_i,
                        annotation=jax.profiler.StepTraceAnnotation,
                        step_num=self._step_i):
            return self._step(inputs, labels)

    def _span(self, name, **kw):
        return telemetry.span(name, tracer=self.tracer, **kw)

    def _step(self, inputs, labels):
        # DecompAware kernels read the prim flag at trace time: a toggle
        # must rebuild, not silently keep the other mode's trace (same
        # contract as to_static's (training, prim) mode token)
        if getattr(self, "_built_prim", None) is not None and \
                self._built_prim != _prim():
            self._compiled = None
            self._gm_compiled = None
            # a partial gradient-merge window would blend gradients
            # traced under both decomposition modes — drop it and
            # restart the window cleanly
            self._gm_accum = None
            self._step_i -= self._step_i % self._gm_k
        first = self._compiled is None and self._gm_compiled is None
        if first:
            with self._span("train_step.build"):
                self._built_prim = _prim()
                if self._gm_k > 1:
                    self._gm_compiled = self._build_gm()
                    for name, fn in zip(("accum_step", "apply_step"),
                                        self._gm_compiled):
                        self.compile_watch.register(name, fn)
                else:
                    self._compiled = self._build()
                    self.compile_watch.register("step", self._compiled)
                import os as _os
                from ..utils.flags import FLAGS
                if getattr(FLAGS, "enable_watchdog", None) or \
                        _os.environ.get("FLAGS_enable_watchdog", "").lower() \
                        in ("1", "true"):
                    from ..distributed.watchdog import enable_watchdog
                    enable_watchdog()
        with self._span("train_step.args"):
            if self.optimizer._state is None:
                self.optimizer._state = self.optimizer.init_state(
                    [p._value for p in self.optimizer._parameter_list])
            p_arrays = [p._value for p in self._p_tensors]
            b_arrays = [b._value for b in self._b_tensors]
            lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
            key = jax.random.fold_in(default_generator._key, self._step_i)

            def _unwrap_batch(x):
                if isinstance(x, Tensor):
                    return (x._value,)
                if isinstance(x, (tuple, list)):
                    return tuple(e._value if isinstance(e, Tensor)
                                 else jnp.asarray(e) for e in x)
                return (jnp.asarray(x),)

            in_arrays = _unwrap_batch(inputs)
            label_arrays = _unwrap_batch(labels)
        if self._gm_k > 1:
            loss, new_params, new_bufs, new_state = self._call_gm(
                p_arrays, b_arrays, lr, key, in_arrays, label_arrays)
        else:
            loss, new_params, new_bufs, new_state = self._dispatch(
                self._compiled, p_arrays, b_arrays, self.optimizer._state,
                lr, key, in_arrays, label_arrays)
        with self._span("train_step.rebind"):
            for b, a in zip(self._b_tensors, new_bufs):
                b._replace(a)
            if new_params is not None:      # the optimizer stepped
                for p, a in zip(self._p_tensors, new_params):
                    p._replace(a)
                self.optimizer._state = new_state
                self.optimizer._step_count += 1
            self._step_i += 1
            from ..distributed.watchdog import notify_step
            notify_step(self._step_i)
        return Tensor(loss)

    def _dispatch(self, fn, *args):
        """Call one step program; on its first call with a shape this
        holds trace + lower + compile, which ``compile_watch`` counts."""
        with self._span("train_step.dispatch") as sp:
            t0 = time.perf_counter()
            out = fn(*args)
            # a compile it finds becomes a span where this one goes
            self.compile_watch.tracer = sp.ring
            self.compile_watch.observe(fn, t0, time.perf_counter(), args)
        return out

    def _call_gm(self, p_arrays, b_arrays, lr, key, in_arrays,
                 label_arrays):
        """One gradient-merge micro-step: accumulate, or (every k-th
        call) merge + optimizer apply. Returns (loss, new_params,
        new_bufs, new_state); new_params is None on an accumulate step:
        the optimizer steps — and its step count / LR schedule advance —
        only on apply."""
        accum_fn, apply_fn = self._gm_compiled
        if self._gm_accum is None:
            self._gm_accum = self._init_gm_accum()
        is_apply = (self._step_i + 1) % self._gm_k == 0
        if not is_apply:
            loss, new_bufs, self._gm_accum = self._dispatch(
                accum_fn, p_arrays, b_arrays, self._gm_accum, key,
                in_arrays, label_arrays)
            return loss, None, new_bufs, None
        loss, new_params, new_bufs, new_state, self._gm_accum = \
            self._dispatch(apply_fn, p_arrays, b_arrays,
                           self.optimizer._state, lr, self._gm_accum, key,
                           in_arrays, label_arrays)
        return loss, new_params, new_bufs, new_state


# ---------------------------------------------------------------------------
# jit.save / jit.load — AOT export parity
# (reference: paddle.jit.save → TranslatedLayer,
# /root/reference/python/paddle/jit/api.py + translated_layer.py). The
# artifact is serialized StableHLO (jax.export) + params npz — loadable
# without the Python model class, like the reference's program+params.
# ---------------------------------------------------------------------------

def save(layer, path, input_spec=None, **configs):
    """Trace layer.forward over input_spec and write <path>.pdmodel
    (StableHLO + metadata) and <path>.pdiparams.npz. Also writes
    <path>.pdparams (state_dict) so paddle.load works on the same
    prefix."""
    import os
    import pickle

    from ..framework.io import save as _save
    from ..static.program import InputSpec

    if input_spec is None:
        raise ValueError(
            "jit.save needs input_spec=[InputSpec(shape, dtype), ...] "
            "to trace the forward (dynamic dims as 1)")
    specs = [s if isinstance(s, InputSpec) else InputSpec(
        s.shape, s.dtype) for s in input_spec]

    params, buffers = _collect(layer)
    p_arrays = [p._value for _, p in params]
    b_arrays = [b._value for _, b in buffers]
    was_training = getattr(layer, "training", False)
    layer.eval()

    def fn(in_arrays, param_arrays, buffer_arrays):
        out, _ = functional_call(layer, param_arrays, buffer_arrays,
                                 tuple(in_arrays))
        flat, _ = jax.tree_util.tree_flatten(out)
        return tuple(flat)

    in_avals = [jax.ShapeDtypeStruct(
        tuple(d if d and d > 0 else 1 for d in s.shape), s.dtype)
        for s in specs]
    p_avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in p_arrays]
    b_avals = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in b_arrays]
    try:
        exported = jax.export.export(jax.jit(fn))(in_avals, p_avals,
                                                  b_avals)
    finally:
        if was_training:
            layer.train()

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".pdmodel", "wb") as f:
        pickle.dump({
            "stablehlo": exported.serialize(),
            "feed_names": [s.name or f"x{i}"
                           for i, s in enumerate(specs)],
            "feed_shapes": [tuple(a.shape) for a in in_avals],
            "feed_dtypes": [str(a.dtype) for a in in_avals],
            "fetch_names": [f"out{i}"
                            for i in range(len(exported.out_avals))],
            "kind": "jit.save",
            "n_params": len(p_arrays),
        }, f)
    np.savez(path + ".pdiparams",
             **{f"p{i}": np.asarray(a)
                for i, a in enumerate(list(p_arrays) + list(b_arrays))})
    _save({"state_dict": layer.state_dict()}, path + ".pdparams")
    return path


class TranslatedLayer:
    """Callable rebuilt from a jit.save artifact (reference
    TranslatedLayer, jit/translated_layer.py) — runs the compiled
    StableHLO, no Python model code needed."""

    def __init__(self, path: str):
        import pickle
        with open(path + ".pdmodel", "rb") as f:
            meta = pickle.load(f)
        self._exported = jax.export.deserialize(meta["stablehlo"])
        z = np.load(path + ".pdiparams.npz")
        stored = [jnp.asarray(z[f"p{i}"]) for i in range(len(z.files))]
        n_p = meta["n_params"]
        self._params = stored[:n_p]
        self._buffers = stored[n_p:]
        self.feed_names = meta["feed_names"]

    def __call__(self, *args):
        in_arrays = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                     for a in args]
        out = self._exported.call(list(in_arrays), self._params,
                                  self._buffers)
        outs = [Tensor(o) for o in out]
        return outs[0] if len(outs) == 1 else tuple(outs)

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only")


def load(path, **configs) -> TranslatedLayer:
    return TranslatedLayer(path)


# --- telemetry/config parity (reference jit/api.py) ------------------------

_to_static_enabled = True


def enable_to_static(enable: bool = True):
    """Globally toggle to_static compilation (reference
    paddle.jit.enable_to_static). When disabled, StaticFunction runs
    its target eagerly."""
    global _to_static_enabled
    _to_static_enabled = bool(enable)


def set_code_level(level=100, also_to_stdout=False):
    """Reference sets dy2static transformed-code logging verbosity; the
    tracing pipeline here has no transformed source to print — the knob
    is accepted and recorded (telemetry lives on StaticFunction:
    retrace_count / trace_signatures / graph_break_count)."""
    import logging
    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if also_to_stdout else logging.INFO)


def set_verbosity(level=0, also_to_stdout=False):
    import logging
    logging.getLogger("paddle_tpu.jit").setLevel(
        logging.DEBUG if level > 0 else logging.WARNING)


__all__ += ["enable_to_static", "set_code_level", "set_verbosity"]
