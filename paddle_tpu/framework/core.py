"""Core Tensor type and eager autograd engine.

TPU-native re-imagination of the reference's eager stack:

- ``Tensor`` is a thin facade over ``jax.Array`` (the reference's
  ``paddle::Tensor``, /root/reference/paddle/phi/api/include/tensor.h:82).
- The eager autograd engine replaces the codegen'd C++ grad nodes
  (/root/reference/paddle/fluid/eager/grad_node_info.h:197 and
  backward.cc:105) with a tape of ``jax.vjp`` closures: every differentiable
  op call records one ``TapeNode``; ``Tensor.backward()`` runs a reverse
  topological sweep, exactly like Paddle's ``RunBackward`` in-degree queue,
  but each node's backward is a JAX VJP (so XLA compiles/fuses the math).
- There is no kernel registry/dispatcher: XLA *is* the kernel library. The
  ``apply`` dispatcher below only does tape recording + AMP autocast, the
  analog of the generated ``xxx_ad_func`` wrappers
  (/root/reference/paddle/fluid/eager/auto_code_generator/generator/eager_gen.py).

Under a JAX trace (the jit/to_static path), the same op implementations run
on tracers; the functional train-step path bypasses the tape entirely and
uses ``jax.grad`` — see paddle_tpu/jit.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes

__all__ = [
    "Tensor",
    "Parameter",
    "apply",
    "no_grad",
    "enable_grad",
    "is_grad_enabled",
    "to_tensor",
    "set_device",
    "get_device",
    "seed",
    "get_rng_state",
    "set_rng_state",
    "default_generator",
    "Generator",
    "with_rng_key",
]


# --------------------------------------------------------------------------
# Grad mode
# --------------------------------------------------------------------------

class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_grad_state = _GradState()


def is_grad_enabled() -> bool:
    return _grad_state.enabled


@contextlib.contextmanager
def no_grad():
    prev = _grad_state.enabled
    _grad_state.enabled = False
    try:
        yield
    finally:
        _grad_state.enabled = prev


@contextlib.contextmanager
def enable_grad():
    prev = _grad_state.enabled
    _grad_state.enabled = True
    try:
        yield
    finally:
        _grad_state.enabled = prev


# --------------------------------------------------------------------------
# Device management
# --------------------------------------------------------------------------

_current_device: Optional[jax.Device] = None


def _resolve_device(spec: str) -> jax.Device:
    spec = spec.lower()
    if ":" in spec:
        kind, idx = spec.split(":")
        idx = int(idx)
    else:
        kind, idx = spec, 0
    # Accept paddle-style names; 'gpu' maps to whatever accelerator is local.
    if kind in ("tpu", "gpu", "xpu", "accelerator"):
        devs = [d for d in jax.devices() if d.platform != "cpu"]
        if not devs:
            raise RuntimeError(
                f"set_device({spec!r}): no accelerator found, jax sees "
                f"only {jax.devices()[0].platform!r} devices")
    elif kind == "cpu":
        devs = jax.devices("cpu")
    else:
        devs = jax.devices()
    return devs[idx % len(devs)]


def set_device(device: str):
    """paddle.set_device analog. Returns the selected jax.Device."""
    global _current_device
    _current_device = _resolve_device(device)
    return _current_device


def get_device() -> str:
    if _current_device is None:
        d = jax.devices()[0]
    else:
        d = _current_device
    name = "cpu" if d.platform == "cpu" else "tpu"
    return f"{name}:{d.id}"


def current_jax_device() -> Optional[jax.Device]:
    return _current_device


# --------------------------------------------------------------------------
# RNG: Paddle-style global seed over JAX threaded PRNG keys.
# Reference: phi::Generator (/root/reference/paddle/phi/core/generator.h) —
# here a splittable key stream; under jit a traced base key can be pushed so
# random ops inside compiled train steps stay functional.
# --------------------------------------------------------------------------

class Generator:
    def __init__(self, seed_: int = 0):
        self._seed = int(seed_)
        self._key_ = None  # lazy: importing the framework must not
        self._traced_key = None  # initialize a JAX backend (launcher CLI,
        self._traced_counter = 0  # fork-based dataloader workers)

    @property
    def _key(self):
        if self._key_ is None:
            self._key_ = jax.random.PRNGKey(self._seed)
        return self._key_

    @_key.setter
    def _key(self, v):
        self._key_ = v

    def manual_seed(self, seed_: int):
        self._seed = int(seed_)
        self._key_ = jax.random.PRNGKey(self._seed)
        return self

    @property
    def initial_seed(self) -> int:
        return self._seed

    def next_key(self):
        """Return a fresh PRNG key. Inside a with_rng_key() scope the keys
        derive from the traced base key (safe under jax.jit); otherwise the
        concrete global key is split."""
        if self._traced_key is not None:
            self._traced_counter += 1
            return jax.random.fold_in(self._traced_key, self._traced_counter)
        self._key, sub = jax.random.split(self._key)
        return sub

    def get_state(self):
        return np.asarray(self._key)

    def set_state(self, state):
        self._key = jnp.asarray(state, dtype=jnp.uint32)
        return self


default_generator = Generator(0)


def seed(value: int):
    """paddle.seed analog."""
    default_generator.manual_seed(value)
    return default_generator


def get_rng_state():
    return default_generator.get_state()


def set_rng_state(state):
    default_generator.set_state(state)


@contextlib.contextmanager
def with_rng_key(key):
    """Thread a (possibly traced) base key through eager-style random ops so
    they remain pure under jax.jit. Used by jit.TrainStep and dropout."""
    prev = (default_generator._traced_key, default_generator._traced_counter)
    default_generator._traced_key = key
    default_generator._traced_counter = 0
    try:
        yield
    finally:
        default_generator._traced_key, default_generator._traced_counter = prev


# --------------------------------------------------------------------------
# Autograd tape
# --------------------------------------------------------------------------

class TapeNode:
    """One recorded differentiable op (analog of a codegen'd GradNode,
    /root/reference/paddle/fluid/eager/grad_node_info.h:197). Holds the
    jax.vjp closure (which owns the saved residuals — the analog of
    TensorWrapper saved tensors) and edges to input tensors."""

    __slots__ = ("vjp_fn", "inputs", "out_avals", "op_name", "id", "multi")

    _counter = 0

    def __init__(self, vjp_fn, inputs, out_avals, op_name, multi=None):
        self.vjp_fn = vjp_fn
        self.inputs = inputs            # List[Tensor] at recorded positions
        self.out_avals = out_avals      # List[jax.ShapeDtypeStruct]
        self.op_name = op_name
        # whether the recorded fn returned a tuple (vjp cotangent structure)
        self.multi = len(out_avals) > 1 if multi is None else multi
        TapeNode._counter += 1
        self.id = TapeNode._counter


def _is_float0(x) -> bool:
    return getattr(x, "dtype", None) == jax.dtypes.float0


def _run_backward(root: "Tensor", grad_arr, retain_graph: bool,
                  accum_fn=None):
    """Reverse topological sweep — analog of egr::RunBackward
    (/root/reference/paddle/fluid/eager/backward.cc:105).

    accum_fn(tensor, grad_array): leaf-gradient sink; defaults to
    Tensor._accum_grad (i.e. populate .grad). paddle.grad() passes a
    collector so it never touches .grad of uninvolved leaves."""
    if accum_fn is None:
        accum_fn = Tensor._accum_grad
    root_node = root._node
    if root_node is None:
        if not root.stop_gradient:
            accum_fn(root, grad_arr)
        return

    # DFS topo order over the node DAG.
    order: List[TapeNode] = []
    visited = set()
    stack = [(root_node, False)]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if node.id in visited:
            continue
        visited.add(node.id)
        stack.append((node, True))
        for t in node.inputs:
            if t._node is not None and t._node.id not in visited:
                stack.append((t._node, False))

    # Seed cotangent.
    node_grads = {root_node.id: [None] * len(root_node.out_avals)}
    node_grads[root_node.id][root._out_idx] = grad_arr

    for node in reversed(order):
        grads = node_grads.pop(node.id, None)
        if grads is None:
            continue
        cotangents = []
        for g, aval in zip(grads, node.out_avals):
            if g is None:
                if np.issubdtype(aval.dtype, np.integer) or \
                        aval.dtype == np.bool_:
                    # non-differentiable output: vjp expects float0
                    cotangents.append(
                        np.zeros(aval.shape, jax.dtypes.float0))
                else:
                    cotangents.append(jnp.zeros(aval.shape, aval.dtype))
            else:
                cotangents.append(g)
        ct = tuple(cotangents) if node.multi else cotangents[0]
        if node.vjp_fn is None:
            raise RuntimeError(
                "Trying to backward through a graph that has already been "
                "freed; call backward(retain_graph=True) to backward twice")
        in_grads = node.vjp_fn(ct)
        for t, g in zip(node.inputs, in_grads):
            if g is None or _is_float0(g):
                continue
            if t._node is not None:
                slot = node_grads.setdefault(t._node.id, [None] * len(t._node.out_avals))
                prev = slot[t._out_idx]
                slot[t._out_idx] = g if prev is None else prev + g
            elif not t.stop_gradient:
                accum_fn(t, g)
        if not retain_graph:
            node.vjp_fn = None

    if not retain_graph:
        for node in order:
            node.inputs = ()


# --------------------------------------------------------------------------
# Tensor
# --------------------------------------------------------------------------

_tensor_method_registry = {}

# When set, Tensor._replace records every mutated Tensor and
# Tensor.__init__ every created one — to_static's plain-function path
# uses this to detect writes to PRE-EXISTING state (buffers/globals)
# that tracing would silently drop (jit/__init__.py).
_mutation_watch = None


class _watch_mutations:
    """Yields (mutated_ids -> Tensor, created_ids) for the with-block."""

    def __enter__(self):
        global _mutation_watch
        self._prev = _mutation_watch
        _mutation_watch = ({}, set())
        return _mutation_watch

    def __exit__(self, *exc):
        global _mutation_watch
        _mutation_watch = self._prev
        return False


class Tensor:
    """Eager tensor: a jax.Array plus autograd metadata.

    ``stop_gradient`` follows Paddle semantics (True by default; Parameters
    default to False). Most methods are monkey-patched from paddle_tpu.tensor
    at import time — mirroring Paddle's math_op_patch
    (/root/reference/python/paddle/base/dygraph/math_op_patch.py:60)."""

    __slots__ = ("_value", "stop_gradient", "grad", "_node", "_out_idx",
                 "name", "persistable", "trainable", "is_leaf_",
                 "process_mesh", "placements", "_opt_state_placements",
                 "__weakref__")

    def __init__(self, value, stop_gradient: bool = True, name: str = ""):
        if _mutation_watch is not None:
            _mutation_watch[1].add(id(self))
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad = None
        self._node = None
        self._out_idx = 0
        self.name = name
        self.persistable = False
        self.trainable = not stop_gradient
        self.is_leaf_ = True
        self.process_mesh = None
        self.placements = None
        # ZeRO-1/2: optimizer-state placements may differ from the
        # param's own (states sharded while params stay replicated)
        self._opt_state_placements = None

    # -- basic properties ---------------------------------------------------
    @property
    def value(self):
        return self._value

    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def ndim(self):
        return self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def dtype(self):
        return np.dtype(self._value.dtype)

    @property
    def is_leaf(self):
        return self._node is None

    @property
    def place(self):
        try:
            dev = next(iter(self._value.devices()))
            return f"{dev.platform}:{dev.id}"
        except Exception:
            return "traced"

    def numpy(self):
        return np.asarray(self._value)

    def item(self):
        return self._value.item()

    def tolist(self):
        return np.asarray(self._value).tolist()

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __repr__(self):
        grad_s = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={dtypes.dtype_name(self.dtype)}"
                f"{grad_s},\n       {np.asarray(jax.device_get(self._value)) if not self._is_traced() else self._value})")

    def _is_traced(self) -> bool:
        return isinstance(self._value, jax.core.Tracer)

    def __bool__(self):
        return bool(self._value)

    def __float__(self):
        return float(self._value)

    def __int__(self):
        return int(self._value)

    def __hash__(self):
        return id(self)

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor: Optional["Tensor"] = None,
                 retain_graph: bool = False):
        """Analog of Tensor.backward →
        /root/reference/paddle/fluid/eager/backward.cc:428 (egr::Backward)."""
        if self.stop_gradient and self._node is None:
            raise RuntimeError("backward() on a tensor with no grad graph")
        if grad_tensor is None:
            if self.size != 1:
                raise RuntimeError(
                    "grad must be provided for non-scalar backward()")
            g = jnp.ones(self._value.shape, self._value.dtype)
        else:
            g = grad_tensor._value if isinstance(grad_tensor, Tensor) else jnp.asarray(grad_tensor)
        _run_backward(self, g, retain_graph)

    def _accum_grad(self, g):
        if g.dtype != self._value.dtype:
            g = g.astype(self._value.dtype)
        if self.grad is None:
            self.grad = Tensor(g, stop_gradient=True, name=self.name + "@GRAD")
        else:
            self.grad = Tensor(self.grad._value + g, stop_gradient=True,
                               name=self.name + "@GRAD")

    def clear_gradient(self, set_to_zero: bool = False):
        if set_to_zero and self.grad is not None:
            self.grad = Tensor(jnp.zeros_like(self.grad._value), True)
        else:
            self.grad = None

    def clear_grad(self):
        self.clear_gradient()

    def detach(self) -> "Tensor":
        return Tensor(self._value, stop_gradient=True, name=self.name)

    def clone(self) -> "Tensor":
        return apply("clone", lambda x: x + 0, self)

    # -- mutation (in-place value replacement) ------------------------------
    def _replace(self, new_value):
        """Replace the underlying array (optimizer updates, buffer updates).
        Breaks no autograd invariants because leaves have no recorded node."""
        if _mutation_watch is not None:
            _mutation_watch[0][id(self)] = self
        # partial-capture placeholders unwrap to their concrete array
        # once materialized (jit/partial_capture._SymValue)
        unwrap = getattr(new_value, "_pt_unwrap", None)
        if unwrap is not None:
            new_value = unwrap()
        self._value = new_value

    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._value
        arr = jnp.asarray(value, dtype=self._value.dtype)
        if tuple(arr.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._value.shape}")
        # keep the destination's sharding (checkpoint load into DistTensor)
        old_sharding = getattr(self._value, "sharding", None)
        if old_sharding is not None and not self._is_traced() and \
                not isinstance(arr, jax.core.Tracer):
            try:
                arr = jax.device_put(arr, old_sharding)
            except Exception:
                pass
        self._replace(arr)

    def copy_(self, other):
        self.set_value(other)
        return self

    # -- conversion ---------------------------------------------------------
    def astype(self, dtype) -> "Tensor":
        d = dtypes.convert_dtype(dtype)
        return apply("cast", lambda x: x.astype(d), self)

    def cast(self, dtype) -> "Tensor":
        return self.astype(dtype)

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a in ("cpu", "tpu", "gpu") or ":" in str(a):
                dev = _resolve_device(str(a))
                t = Tensor(jax.device_put(t._value, dev), t.stop_gradient, t.name)
            else:
                t = t.astype(a)
        return t

    def cpu(self):
        return Tensor(jax.device_get(self._value), self.stop_gradient, self.name)

    def pin_memory(self):
        return self

    def cuda(self):  # paddle API compat; routes to the accelerator
        return self.to("tpu")

    # -- registration hook for monkey patching ------------------------------
    @classmethod
    def _register_method(cls, name: str, fn: Callable):
        _tensor_method_registry[name] = fn
        setattr(cls, name, fn)


class Parameter(Tensor):
    """Trainable leaf tensor (analog of paddle's ParamBase /
    EagerParamBase). stop_gradient defaults to False."""

    def __init__(self, value, trainable: bool = True, name: str = ""):
        super().__init__(value, stop_gradient=not trainable, name=name)
        self.persistable = True
        self.trainable = trainable

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


# --------------------------------------------------------------------------
# Op dispatch: record-on-tape wrapper.
# --------------------------------------------------------------------------

def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def as_jnp(x):
    """Coerce Tensor / ndarray / python scalar to a jnp array."""
    return jnp.asarray(_unwrap(x))


_amp_hook: Optional[Callable] = None  # installed by paddle_tpu.amp


def _set_amp_hook(fn):
    global _amp_hook
    _amp_hook = fn


# Static-graph recorder hook (installed by paddle_tpu.static): when static
# mode is on and any arg is symbolic, ops append graph nodes instead of
# executing — the analog of OpDesc appending to the default main Program
# (/root/reference/python/paddle/base/framework.py), except the "IR" is a
# DAG of pure jax thunks and shape inference is jax.eval_shape.
_static_handler: Optional[Callable] = None


def _set_static_handler(fn):
    global _static_handler
    _static_handler = fn


# Partial-graph capture handler (jit/partial_capture.py — the SOT analog:
# /root/reference/python/paddle/jit/sot/opcode_translator/executor/
# opcode_executor.py). Receives (op_name, fn, args, kwargs, diff);
# NotImplemented defers to the normal eager path.
_capture_handler: Optional[Callable] = None


def _set_capture_handler(fn):
    global _capture_handler
    _capture_handler = fn


# Numerics-checker + op-stats hooks (installed by paddle_tpu.amp.debugging
# — the FLAGS_check_nan_inf / op-stats analog of the reference's
# paddle/fluid/eager/nan_inf_utils.h). Both receive (op_name, out_arrays).
_check_hook: Optional[Callable] = None
_stats_hook: Optional[Callable] = None


def _set_check_hook(fn):
    global _check_hook
    _check_hook = fn


def _set_stats_hook(fn):
    global _stats_hook
    _stats_hook = fn


def apply(op_name: str, fn: Callable, *args: Any, **kwargs: Any):
    """Run ``fn`` over the unwrapped jax arrays of ``args``, recording a
    TapeNode when gradients are required. ``fn`` must be pure; non-Tensor
    args pass through as captured constants.

    This is the analog of one generated ``xxx_ad_func``
    (/root/reference/paddle/fluid/eager/auto_code_generator/generator/eager_gen.py):
    AMP autocast → (optional) grad-node creation → kernel invocation, except
    the 'kernel' is a jnp/lax composition compiled by XLA.
    """
    if _static_handler is not None:
        out = _static_handler(op_name, fn, args, kwargs)
        if out is not NotImplemented:
            return out
    if _capture_handler is not None:
        out = _capture_handler(op_name, fn, args, kwargs, True)
        if out is not NotImplemented:
            return out
    tensor_pos = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    tensors = [args[i] for i in tensor_pos]

    if _amp_hook is not None:
        tensors = _amp_hook(op_name, tensors)

    arrs = tuple(t._value for t in tensors)

    def pure(*xs):
        full = list(args)
        for i, x in zip(tensor_pos, xs):
            full[i] = x
        return fn(*full, **kwargs)

    need_grad = (_grad_state.enabled
                 and any(not t.stop_gradient for t in tensors))

    if need_grad:
        outs, vjp_fn = jax.vjp(pure, *arrs)
    else:
        outs = pure(*arrs)

    multi = isinstance(outs, (tuple, list))
    outs_list = list(outs) if multi else [outs]

    if _check_hook is not None:
        _check_hook(op_name, outs_list)
    if _stats_hook is not None:
        _stats_hook(op_name, outs_list)

    result = [Tensor(o, stop_gradient=not need_grad) for o in outs_list]

    if need_grad:
        node = TapeNode(
            vjp_fn,
            tensors,
            [jax.ShapeDtypeStruct(o.shape, o.dtype) for o in outs_list],
            op_name,
            multi=multi,
        )
        for k, t in enumerate(result):
            t._node = node
            t._out_idx = k
            t.is_leaf_ = False

    if multi:
        return tuple(result)
    return result[0]


def apply_nodiff(op_name: str, fn: Callable, *args, **kwargs):
    """Dispatch for non-differentiable ops (argmax, comparisons, ...)."""
    if _static_handler is not None:
        out = _static_handler(op_name, fn, args, kwargs)
        if out is not NotImplemented:
            return out
    if _capture_handler is not None:
        out = _capture_handler(op_name, fn, args, kwargs, False)
        if out is not NotImplemented:
            return out
    tensor_pos = [i for i, a in enumerate(args) if isinstance(a, Tensor)]

    full = list(args)
    for i in tensor_pos:
        full[i] = args[i]._value
    outs = fn(*full, **kwargs)
    multi = isinstance(outs, (tuple, list))
    outs_list = list(outs) if multi else [outs]
    if _check_hook is not None:
        _check_hook(op_name, outs_list)
    if _stats_hook is not None:
        _stats_hook(op_name, outs_list)
    result = [Tensor(o, stop_gradient=True) for o in outs_list]
    return tuple(result) if multi else result[0]


# --------------------------------------------------------------------------
# Creation
# --------------------------------------------------------------------------

def to_tensor(data, dtype=None, place=None, stop_gradient: bool = True) -> Tensor:
    """paddle.to_tensor analog."""
    if isinstance(data, Tensor):
        arr = data._value
        if dtype is not None:
            arr = arr.astype(dtypes.convert_dtype(dtype))
        return Tensor(arr, stop_gradient=stop_gradient)
    d = dtypes.convert_dtype(dtype) if dtype is not None else None
    if d is None and isinstance(data, (float,)):
        d = dtypes.get_default_dtype()
    if d is None and isinstance(data, (list, tuple)) and _contains_float(data):
        d = dtypes.get_default_dtype()
    if d is None and isinstance(data, np.ndarray) and data.dtype == np.float64:
        d = dtypes.get_default_dtype()
    arr = jnp.asarray(data, dtype=d)
    dev = _resolve_device(place) if isinstance(place, str) else _current_device
    if dev is not None and not isinstance(arr, jax.core.Tracer):
        arr = jax.device_put(arr, dev)
    return Tensor(arr, stop_gradient=stop_gradient)


def _contains_float(x) -> bool:
    if isinstance(x, float):
        return True
    if isinstance(x, (list, tuple)):
        return any(_contains_float(e) for e in x)
    return False
