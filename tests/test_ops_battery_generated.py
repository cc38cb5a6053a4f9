"""GENERATED op battery over the full public op surface (VERDICT r3 #8;
reference: /root/reference/test/legacy_test/op_test.py:420,2973 — every
op gets per-dtype output checks and numeric-vs-analytic gradients).

The hand-written battery (test_ops_battery.py) checks ~100 core ops
against numpy references. This file closes the breadth gap: EVERY public
callable of the `paddle` tensor namespace and `nn.functional` is either

  1. auto-probed: synthesized inputs (from `SPECS` or the default
     float-tensor heuristics) run the op through
       - eager execution (finite outputs) — every eager op is a jax
         composition, so this also exercises the tracing seam,
       - analytic-vs-numeric gradient (float→float ops, f32),
       - a bf16 tier (op accepts bf16 inputs; matches f32 within bf16
         tolerance) unless listed in `NO_BF16`,
  2. or listed in `EXCLUDED` with a reason (not a tensor op: factories,
     state management, io, ...; or covered by a dedicated suite).

A surface-accounting test enforces the partition: adding a public op
without a spec or an exclusion row FAILS the build (coverage ratchet —
the reference regenerates its op tests from the op registry; here the
registry IS the public namespace).
"""
from __future__ import annotations

import inspect

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.core import Tensor

rng = np.random.RandomState(11)


def T(*shape, lo=0.1, hi=1.1, dtype=np.float32):
    """Positive-valued tensor (keeps log/sqrt/rsqrt/pow domains legal)."""
    return paddle.to_tensor(
        (rng.rand(*shape) * (hi - lo) + lo).astype(dtype))


def Tsigned(*shape, dtype=np.float32):
    return paddle.to_tensor(rng.randn(*shape).astype(dtype))


def Ti(*shape, n=6):
    return paddle.to_tensor(rng.randint(0, n, shape).astype(np.int64))


def Tb(*shape):
    return paddle.to_tensor(rng.rand(*shape) > 0.5)


# ---------------------------------------------------------------------------
# the spec/exclusion tables are populated from the surface probe; see
# `_surface()` + test_surface_fully_partitioned below
# ---------------------------------------------------------------------------

# name -> dict(args=callable returning a tuple of args,
#              kwargs=dict (optional),
#              grad=False to skip the gradient check (non-differentiable
#                   or intentionally integer/bool semantics),
#              bf16=False to skip the bf16 tier)
SPECS: dict = {}

# name -> reason. These are NOT silently dropped ops: each row says why
# the generated battery does not exercise it (factory/state/io/control
# surfaces, random ops, and ops with dedicated suites).
EXCLUDED: dict = {}

# float ops whose bf16 tier is skipped (dtype-strict kernels)
NO_BF16: set = set()


def _surface():
    out = []
    for modname, mod in (("paddle", paddle), ("F", F)):
        for name in sorted(dir(mod)):
            if name.startswith("_"):
                continue
            fn = getattr(mod, name)
            if (not callable(fn) or inspect.isclass(fn)
                    or inspect.ismodule(fn)):
                continue
            out.append((f"{modname}.{name}", fn))
    return out


SURFACE = _surface()
_BY_NAME = dict(SURFACE)

# -- exclusions (each row says WHY the generated battery skips it) ----------

_R_FACTORY = "tensor factory / random sampler: no input-output contract to check here; shape/dtype covered in test_tensor_ops"
_R_STATE = "framework/device/RNG state management, not a tensor op"
_R_IO = "io/introspection surface, covered by its own suite"
_R_ALIAS = "in-place alias of the checked out-of-place op (same kernel)"
_R_DED = "covered by a dedicated suite"

EXCLUDED.update({
    # dispatch internals
    "paddle.apply": "the dispatcher itself, not an op",
    "paddle.apply_nodiff": "the dispatcher itself, not an op",
    # factories / random
    **{f"paddle.{n}": _R_FACTORY for n in (
        "arange", "empty", "eye", "full", "full_like", "linspace",
        "logspace", "ones", "zeros", "create_parameter", "tril_indices",
        "triu_indices", "rand", "randint", "randint_like", "randn",
        "randperm", "standard_normal", "uniform", "top_p_sampling")},
    # state / device / grad-mode / flags
    **{f"paddle.{n}": _R_STATE for n in (
        "seed", "set_device", "set_flags", "get_flags", "get_device",
        "device_count", "get_default_dtype", "get_cuda_rng_state",
        "set_cuda_rng_state", "set_rng_state", "get_rng_state",
        "set_grad_enabled", "enable_grad", "enable_static", "no_grad",
        "grad", "in_dynamic_mode", "is_grad_enabled",
        "is_compiled_with_cuda", "is_compiled_with_tpu",
        "is_compiled_with_xpu", "disable_signal_handler",
        "set_printoptions", "iinfo")},
    # io / model utilities
    "paddle.save": _R_IO, "paddle.load": _R_IO,
    "paddle.summary": _R_IO, "paddle.flops": _R_IO,
    "paddle.geometric_": "namespace re-export (paddle.geometric), not an op",
    "paddle.broadcast_shape": "shape-arithmetic helper (no tensors)",
    # in-place aliases
    **{f"paddle.{n}_": _R_ALIAS for n in (
        "addmm", "bitwise_and", "bitwise_left_shift", "bitwise_not",
        "bitwise_or", "bitwise_right_shift", "bitwise_xor", "gcd",
        "lcm", "lerp", "index_add", "index_fill", "index_put",
        "masked_fill", "masked_scatter", "multigammaln", "polygamma",
        "put_along_axis", "renorm", "reshape", "scatter", "transpose",
        "unsqueeze", "where")},
    # dedicated suites
    "F.flash_attention": _R_DED + " (test_varlen_attention)",
    "F.flash_attn_unpadded": _R_DED + " (test_varlen_attention)",
    "F.scaled_dot_product_attention": _R_DED + " (test_varlen_attention)",
    "F.sparse_attention": "loud descope (COVERAGE.md)",
    "F.ctc_loss": _R_DED + " (test_functional_extras grad battery)",
    "F.rnnt_loss": _R_DED + " (test_functional_extras)",
    "F.gather_tree": _R_DED + " (test_domain_libs beam decode)",
    "F.chunked_softmax_cross_entropy": _R_DED + " (test_models)",
    "F.class_center_sample": "random sampler (distributed margin-loss aux)",
    "paddle.pca_lowrank": "randomized algorithm " + _R_DED,
    "paddle.stft": _R_DED + " (test_functional_extras signal suite)",
    "paddle.istft": _R_DED + " (test_functional_extras signal suite)",
})

# -- specs for ops whose inputs need shaping --------------------------------

def _sq():          # square PSD matrix (cholesky/inv/eig domains)
    a = rng.randn(4, 4).astype(np.float32)
    return paddle.to_tensor(a @ a.T + 4 * np.eye(4, dtype=np.float32))

def _img():         # NCHW activation
    return T(2, 3, 8, 8)

def _conv_w(cout, cin, k):
    return paddle.to_tensor(
        (rng.randn(cout, cin, k, k) * 0.2).astype(np.float32))

SPECS.update({
    # matmul family / shape pairs
    "paddle.matmul": dict(args=lambda: (T(3, 4), T(4, 5))),
    "paddle.mm": dict(args=lambda: (T(3, 4), T(4, 5))),
    "paddle.bmm": dict(args=lambda: (T(2, 3, 4), T(2, 4, 5))),
    "paddle.mv": dict(args=lambda: (T(3, 4), T(4))),
    "paddle.addmm": dict(args=lambda: (T(3, 5), T(3, 4), T(4, 5))),
    "paddle.einsum": dict(args=lambda: ("ij,jk->ik", T(3, 4), T(4, 5))),
    "paddle.multi_dot": dict(args=lambda: ([T(3, 4), T(4, 5), T(5, 2)],)),
    "paddle.outer": dict(args=lambda: (T(3), T(4))),
    # linalg (square / PSD)
    "paddle.cholesky": dict(args=lambda: (_sq(),)),
    "paddle.cholesky_solve": dict(args=lambda: (T(4, 2), paddle.cholesky(_sq()))),
    "paddle.det": dict(args=lambda: (_sq(),)),
    "paddle.slogdet": dict(args=lambda: (_sq(),)),
    "paddle.inv": dict(args=lambda: (_sq(),)),
    "paddle.inverse": dict(args=lambda: (_sq(),)),
    "paddle.matrix_power": dict(args=lambda: (_sq(), 2)),
    "paddle.eig": dict(args=lambda: (_sq(),), grad=False, bf16=False),
    "paddle.eigh": dict(args=lambda: (_sq(),), grad=False, bf16=False),
    "paddle.eigvals": dict(args=lambda: (_sq(),), grad=False, bf16=False),
    "paddle.eigvalsh": dict(args=lambda: (_sq(),), grad=False, bf16=False),
    "paddle.solve": dict(args=lambda: (_sq(), T(4, 2))),
    "paddle.triangular_solve": dict(
        args=lambda: (paddle.cholesky(_sq()), T(4, 2)),
        kwargs=dict(upper=False)),
    "paddle.householder_product": dict(
        args=lambda: (T(4, 3), T(3)), grad=False, bf16=False),
    "paddle.renorm": dict(args=lambda: (T(3, 4), 1.0, 0, 2.0)),
    # shape / movement (need axis/shape args)
    "paddle.reshape": dict(args=lambda: (T(3, 4), [4, 3])),
    "paddle.transpose": dict(args=lambda: (T(3, 4), [1, 0])),
    "paddle.swapaxes": dict(args=lambda: (T(3, 4), 0, 1)),
    "paddle.moveaxis": dict(args=lambda: (T(3, 4), 0, 1)),
    "paddle.unsqueeze": dict(args=lambda: (T(3, 4), 1)),
    "paddle.expand": dict(args=lambda: (T(1, 4), [3, 4])),
    "paddle.broadcast_to": dict(args=lambda: (T(1, 4), [3, 4])),
    "paddle.tile": dict(args=lambda: (T(3, 4), [2, 1])),
    "paddle.flip": dict(args=lambda: (T(3, 4), [0])),
    "paddle.roll": dict(args=lambda: (T(3, 4), 1)),
    "paddle.reverse": dict(args=lambda: (T(3, 4), [1])),
    "paddle.slice": dict(args=lambda: (T(3, 4), [0], [0], [2])),
    "paddle.strided_slice": dict(
        args=lambda: (T(3, 4), [0], [0], [3], [2])),
    "paddle.crop": dict(args=lambda: (T(3, 4), [2, 2], [0, 1])),
    "paddle.as_strided": dict(args=lambda: (T(3, 4), [2, 2], [4, 1])),
    "paddle.unflatten": dict(args=lambda: (T(3, 4), 1, [2, 2])),
    "paddle.unfold": dict(args=lambda: (T(3, 8), 1, 3, 2)),
    "paddle.pad": dict(args=lambda: (T(3, 4), [1, 1])),
    # list-input ops (the HANG rows: iterating a Tensor was the trap)
    "paddle.concat": dict(args=lambda: ([T(2, 3), T(2, 3)],)),
    "paddle.stack": dict(args=lambda: ([T(2, 3), T(2, 3)],)),
    "paddle.vstack": dict(args=lambda: ([T(2, 3), T(2, 3)],)),
    "paddle.hstack": dict(args=lambda: ([T(2, 3), T(2, 3)],)),
    "paddle.dstack": dict(args=lambda: ([T(2, 3), T(2, 3)],)),
    "paddle.column_stack": dict(args=lambda: ([T(3), T(3)],)),
    "paddle.row_stack": dict(args=lambda: ([T(2, 3), T(2, 3)],)),
    "paddle.broadcast_tensors": dict(
        args=lambda: ([T(1, 3), T(2, 1)],)),
    "paddle.meshgrid": dict(args=lambda: ([T(3), T(4)],)),
    "paddle.multiplex": dict(
        args=lambda: ([T(3, 4), T(3, 4)],
                      paddle.to_tensor(np.array([0, 1, 0]))),
        grad=False),
    "paddle.chunk": dict(args=lambda: (T(4, 6), 2)),
    "paddle.split": dict(args=lambda: (T(4, 6), 2)),
    "paddle.tensor_split": dict(args=lambda: (T(4, 6), 2)),
    "paddle.hsplit": dict(args=lambda: (T(4, 6), 2)),
    "paddle.vsplit": dict(args=lambda: (T(4, 6), 2)),
    "paddle.dsplit": dict(args=lambda: (T(2, 2, 4), 2)),
    # reductions / quantiles that hung on eager-iteration
    "paddle.quantile": dict(args=lambda: (T(3, 8), 0.5)),
    "paddle.nanquantile": dict(args=lambda: (T(3, 8), 0.5)),
    "paddle.kthvalue": dict(args=lambda: (T(3, 8), 2)),
    "paddle.topk": dict(args=lambda: (T(3, 8), 2)),
    # indexing family
    "paddle.gather": dict(args=lambda: (T(5, 4), Ti(3, n=5))),
    "paddle.gather_nd": dict(
        args=lambda: (T(4, 5), paddle.to_tensor(
            np.array([[0], [2]], np.int64)))),
    "paddle.index_select": dict(args=lambda: (T(5, 4), Ti(3, n=5))),
    "paddle.index_sample": dict(args=lambda: (T(3, 6), Ti(3, 2, n=6))),
    "paddle.index_add": dict(
        args=lambda: (T(5, 4), Ti(3, n=5), 0, T(3, 4))),
    "paddle.index_fill": dict(
        args=lambda: (T(5, 4), Ti(2, n=5), 0, 1.0)),
    "paddle.index_put": dict(
        args=lambda: (T(5, 4), (Ti(2, n=5),), T(2, 4))),
    "paddle.take": dict(args=lambda: (T(4, 5), Ti(3, n=20))),
    "paddle.take_along_axis": dict(
        args=lambda: (T(3, 6), Ti(3, 2, n=6), 1)),
    "paddle.put_along_axis": dict(
        args=lambda: (T(3, 6), Ti(3, 2, n=6), T(3, 2), 1)),
    "paddle.masked_select": dict(args=lambda: (T(3, 4), Tb(3, 4)),
                                 grad=False),
    "paddle.masked_fill": dict(args=lambda: (T(3, 4), Tb(3, 4), 0.5)),
    "paddle.masked_scatter": dict(
        args=lambda: (T(3, 4), Tb(3, 4), T(12))),
    "paddle.scatter": dict(
        args=lambda: (T(5, 4), Ti(3, n=5), T(3, 4))),
    "paddle.scatter_nd": dict(
        args=lambda: (paddle.to_tensor(np.array([[1], [3]], np.int64)),
                      T(2, 4), [5, 4])),
    "paddle.scatter_nd_add": dict(
        args=lambda: (T(5, 4), paddle.to_tensor(
            np.array([[1], [3]], np.int64)), T(2, 4))),
    "paddle.select_scatter": dict(
        args=lambda: (T(3, 4), T(4), 0, 1)),
    "paddle.slice_scatter": dict(
        args=lambda: (T(5, 4), T(2, 4)),
        kwargs=dict(axes=[0], starts=[0], ends=[2], strides=[1])),
    "paddle.diagonal_scatter": dict(args=lambda: (T(4, 4), T(4))),
    "paddle.shard_index": dict(
        args=lambda: (Ti(4, 1, n=8), 8, 2, 0), grad=False),
    "paddle.repeat_interleave": dict(args=lambda: (T(3, 4), 2)),
    # int / bool ops
    **{f"paddle.{n}": dict(args=lambda: (Ti(3, 4), Ti(3, 4)),
                           grad=False, bf16=False)
       for n in ("bitwise_and", "bitwise_or", "bitwise_xor",
                 "bitwise_left_shift", "bitwise_right_shift", "gcd",
                 "lcm")},
    "paddle.bitwise_not": dict(args=lambda: (Ti(3, 4),), grad=False,
                               bf16=False),
    "paddle.bincount": dict(args=lambda: (Ti(8, n=5),), grad=False,
                            bf16=False),
    # misc math with extra args
    "paddle.lerp": dict(args=lambda: (T(3, 4), T(3, 4), 0.3)),
    "paddle.multigammaln": dict(args=lambda: (T(3, 4, lo=3.0, hi=6.0), 2)),
    "paddle.polygamma": dict(args=lambda: (T(3, 4), 1)),
    "paddle.vander": dict(args=lambda: (T(4), 3)),
    # F.* losses / nn ops
    "F.linear": dict(args=lambda: (T(3, 4), T(4, 5))),
    "F.bilinear": dict(args=lambda: (T(3, 4), T(3, 5), T(2, 4, 5))),
    "F.embedding": dict(args=lambda: (Ti(3, 4, n=6), T(6, 5))),
    "F.one_hot": dict(args=lambda: (Ti(3, 4, n=5), 5), grad=False,
                      bf16=False),
    "F.nll_loss": dict(
        args=lambda: (F.log_softmax(Tsigned(4, 5)), Ti(4, n=5))),
    "F.cosine_embedding_loss": dict(
        args=lambda: (T(4, 5), T(4, 5), paddle.to_tensor(
            np.array([1, -1, 1, 1], np.int64)))),
    "F.margin_ranking_loss": dict(
        args=lambda: (T(4), T(4), paddle.to_tensor(
            np.array([1., -1., 1., 1.], np.float32)))),
    "F.multi_margin_loss": dict(args=lambda: (T(4, 5), Ti(4, n=5))),
    "F.triplet_margin_loss": dict(
        args=lambda: (T(4, 5), T(4, 5), T(4, 5))),
    "F.triplet_margin_with_distance_loss": dict(
        args=lambda: (T(4, 5), T(4, 5), T(4, 5))),
    "F.gaussian_nll_loss": dict(
        args=lambda: (T(4, 5), T(4, 5), T(4, 5))),
    "F.npair_loss": dict(args=lambda: (T(4, 5), T(4, 5), Ti(4, n=3))),
    "F.hsigmoid_loss": dict(
        args=lambda: (T(4, 5), Ti(4, n=6), 6, T(5, 5), T(5)),
        grad=False),
    "F.margin_cross_entropy": dict(
        args=lambda: (T(4, 5), Ti(4, n=5)), grad=False),
    # convs / pools (NCHW)
    "F.conv1d": dict(args=lambda: (T(2, 3, 8), paddle.to_tensor(
        (rng.randn(4, 3, 3) * 0.2).astype(np.float32)))),
    "F.conv2d": dict(args=lambda: (_img(), _conv_w(4, 3, 3))),
    "F.conv3d": dict(args=lambda: (T(1, 2, 6, 6, 6), paddle.to_tensor(
        (rng.randn(3, 2, 2, 2, 2) * 0.2).astype(np.float32)))),
    "F.conv1d_transpose": dict(
        args=lambda: (T(2, 3, 8), paddle.to_tensor(
            (rng.randn(3, 4, 3) * 0.2).astype(np.float32)))),
    "F.conv2d_transpose": dict(
        args=lambda: (_img(), paddle.to_tensor(
            (rng.randn(3, 4, 3, 3) * 0.2).astype(np.float32)))),
    "F.conv3d_transpose": dict(
        args=lambda: (T(1, 2, 6, 6, 6), paddle.to_tensor(
            (rng.randn(2, 3, 2, 2, 2) * 0.2).astype(np.float32)))),
    **{f"F.{n}": dict(args=lambda: (_img(), 2))
       for n in ("avg_pool2d", "max_pool2d")},
    "F.avg_pool1d": dict(args=lambda: (T(2, 3, 8), 2)),
    "F.max_pool1d": dict(args=lambda: (T(2, 3, 8), 2)),
    "F.avg_pool3d": dict(args=lambda: (T(1, 2, 4, 4, 4), 2)),
    "F.max_pool3d": dict(args=lambda: (T(1, 2, 4, 4, 4), 2)),
    **{f"F.adaptive_{n}_pool1d": dict(args=lambda: (T(2, 3, 8), 2))
       for n in ("avg", "max")},
    **{f"F.adaptive_{n}_pool2d": dict(args=lambda: (_img(), 2))
       for n in ("avg", "max")},
    **{f"F.adaptive_{n}_pool3d": dict(
        args=lambda: (T(1, 2, 4, 4, 4), 2)) for n in ("avg", "max")},
    "F.max_unpool1d": dict(
        args=lambda: F.max_pool1d(T(2, 3, 8), 2, return_mask=True)
        + (2,), grad=False),
    "F.max_unpool2d": dict(
        args=lambda: F.max_pool2d(_img(), 2, return_mask=True) + (2,),
        grad=False),
    "F.max_unpool3d": dict(
        args=lambda: F.max_pool3d(T(1, 2, 4, 4, 4), 2,
                                  return_mask=True) + (2,),
        grad=False),
    "F.fractional_max_pool2d": dict(args=lambda: (_img(), 2),
                                    grad=False),
    "F.fractional_max_pool3d": dict(
        args=lambda: (T(1, 2, 4, 4, 4), 2), grad=False),
    "F.maxout": dict(args=lambda: (T(2, 4, 6, 6), 2)),
    # norms (weight/bias/stat args)
    "F.batch_norm": dict(
        args=lambda: (_img(), paddle.zeros([3]), paddle.ones([3]),
                      paddle.ones([3]), paddle.zeros([3]))),
    "F.layer_norm": dict(args=lambda: (T(3, 8), [8])),
    "F.group_norm": dict(args=lambda: (T(2, 4, 6, 6), 2)),
    "F.local_response_norm": dict(args=lambda: (_img(), 3)),
    "F.prelu": dict(args=lambda: (Tsigned(2, 3, 4, 4), T(3))),
    # image / spatial
    "F.affine_grid": dict(
        args=lambda: (T(2, 2, 3), [2, 3, 6, 6]), bf16=False),
    "F.grid_sample": dict(
        args=lambda: (_img(), paddle.to_tensor(
            (rng.rand(2, 8, 8, 2) * 2 - 1).astype(np.float32)))),
    "F.pixel_shuffle": dict(args=lambda: (T(2, 4, 3, 3), 2)),
    "F.pixel_unshuffle": dict(args=lambda: (T(2, 1, 6, 6), 2)),
    "F.channel_shuffle": dict(args=lambda: (T(2, 4, 3, 3), 2)),
    "F.temporal_shift": dict(args=lambda: (T(4, 4, 3, 3), 2, 0.25)),
    "F.pad": dict(args=lambda: (T(3, 4), [1, 1])),
    "F.zeropad2d": dict(args=lambda: (_img(), [1, 1, 1, 1])),
    "F.unfold": dict(args=lambda: (_img(), 3)),
    "F.fold": dict(
        args=lambda: (T(2, 27, 4), [4, 4], [3, 3]),
    ),
})

# ---------------------------------------------------------------------------
# auto-probe defaults for everything not in SPECS/EXCLUDED
# ---------------------------------------------------------------------------

def _spec_for(name):
    sp = SPECS.get(name)
    if sp is not None:
        return sp
    return dict(args=None)     # default probe: unary then binary floats


def _make_args(name):
    sp = _spec_for(name)
    if sp.get("args") is not None:
        return sp["args"](), sp.get("kwargs", {})
    fn = _BY_NAME[name]
    for args in ((T(3, 4),), (T(3, 4), T(3, 4))):
        try:
            fn(*args)
            return args, {}
        except Exception:
            continue
    raise AssertionError(
        f"{name}: default probe failed — add a SPECS or EXCLUDED row")


def _flat_np(out):
    if isinstance(out, Tensor):
        return [np.asarray(out._value)]
    if isinstance(out, (tuple, list)):
        flat = []
        for o in out:
            flat.extend(_flat_np(o))
        return flat
    return [np.asarray(out)] if hasattr(out, "shape") else []


# in-place variants: auto-excluded when their out-of-place base op is on
# the surface (same kernel; in-place mutation breaks the re-evaluation
# the numeric-grad probe needs)
_NAMES = {n for n, _ in SURFACE}
for _n in list(_NAMES):
    if _n.endswith("_") and (_n[:-1] in _NAMES or _n in (
            "paddle.cauchy_", "paddle.exponential_", "paddle.normal_",
            "paddle.uniform_", "paddle.where_", "F.elu_",
            "F.hardtanh_", "F.leaky_relu_", "F.relu_", "F.softmax_",
            "F.tanh_", "F.thresholded_relu_")):
        EXCLUDED.setdefault(_n, _R_ALIAS + " / in-place random fill")

# like-factories discovered by the probe
EXCLUDED.update({
    **{f"paddle.{n}": _R_FACTORY for n in (
        "zeros_like", "ones_like", "empty_like", "rand_like",
        "randn_like", "to_tensor", "create_tensor", "normal",
        "bernoulli", "poisson", "standard_gamma", "multinomial",
        "assign")},
})
EXCLUDED["paddle.assign"] = (
    "copy op: detaches by reference semantics; covered in "
    "test_tensor_ops")

# random ops: output AND grads change per draw — only the finite check
SPECS.update({
    **{f"F.{n}": dict(grad=False, bf16=False, args=None)
       for n in ("dropout", "dropout2d", "dropout3d", "alpha_dropout",
                 "gumbel_softmax")},
    # domain-restricted inputs
    **{f"paddle.{n}": dict(args=lambda: (paddle.to_tensor(
        (rng.rand(3, 4) * 1.6 - 0.8).astype(np.float32)),))
       for n in ("acos", "asin", "atanh", "erfinv")},
    "paddle.acosh": dict(args=lambda: (T(3, 4, lo=1.2, hi=3.0),)),
    "paddle.logit": dict(args=lambda: (T(3, 4, lo=0.2, hi=0.8),)),
    "F.log_loss": dict(args=lambda: (T(3, 4, lo=0.2, hi=0.8),
                                     T(3, 4, lo=0.2, hi=0.8))),
    "F.binary_cross_entropy": dict(
        args=lambda: (T(3, 4, lo=0.2, hi=0.8),
                      T(3, 4, lo=0.2, hi=0.8))),
    "paddle.pad": dict(args=lambda: (_img(), [1, 1, 1, 1])),
    "F.pad": dict(args=lambda: (_img(), [1, 1, 1, 1])),
    # tall matrix: jax's QR derivative needs rows >= cols; grad is
    # skipped — Q/R are unique only up to column signs, so a finite
    # perturbation can flip a sign and break central differences
    "paddle.qr": dict(args=lambda: (T(4, 3),), bf16=False, grad=False),
    "paddle.lu_unpack": dict(
        args=lambda: paddle.lu(_sq())[:2], grad=False, bf16=False),
    # integer / discontinuous semantics: zero-or-undefined gradients
    **{f"paddle.{n}": dict(args=None, grad=False)
       for n in ("sign", "floor_divide", "unique",
                 "unique_consecutive", "nextafter")},
    # masked_scatter: grad through boolean advanced indexing is not
    # taped (known gap — output check only)
    "paddle.masked_scatter": dict(
        args=lambda: (T(3, 4), Tb(3, 4), T(12)), grad=False),
    # pdist: sqrt of near-zero pair distances is numerically unstable
    # under central differences — output + bf16 only
    "paddle.pdist": dict(args=None, grad=False),
    "paddle.increment": dict(args=None, bf16=False),
})

# linalg kernels are f32-only on the jax side (loud NotImplementedError
# on bf16 inputs)
NO_BF16.update({
    "paddle.cholesky", "paddle.cholesky_solve", "paddle.cond",
    "paddle.det", "paddle.inv", "paddle.inverse", "paddle.pinv",
    "paddle.slogdet", "paddle.solve", "paddle.svd", "paddle.lu",
    "paddle.matrix_power", "paddle.triangular_solve",
    "paddle.matrix_rank", "paddle.lstsq", "paddle.ormqr",
    # discontinuous at multiples of the divisor: a bf16 rounding of the
    # quotient jumps the result by a full divisor
    "paddle.mod",
})

TESTABLE = sorted(name for name, _ in SURFACE if name not in EXCLUDED)


def test_surface_fully_partitioned():
    """Coverage ratchet: every public op is tested or loudly excluded."""
    names = {name for name, _ in SURFACE}
    stale = (set(EXCLUDED) | set(SPECS)) - names
    assert not stale, f"table rows for nonexistent ops: {sorted(stale)}"
    # the battery must cover at least the reference-scale op surface
    assert len(TESTABLE) >= 340, len(TESTABLE)


@pytest.mark.parametrize("name", TESTABLE)
def test_op(name):
    import jax
    import zlib

    # per-op deterministic inputs: reseeding the shared module rng makes
    # a failure reproducible under `pytest -k op` regardless of which
    # tests ran before (the spec lambdas all draw from `rng`)
    rng.seed(zlib.crc32(name.encode()) % (2 ** 31))
    fn = _BY_NAME[name]
    sp = _spec_for(name)
    args, kwargs = _make_args(name)

    # 1. eager: runs, outputs finite
    out = fn(*args, **kwargs)
    outs = _flat_np(out)
    for o in outs:
        if np.issubdtype(o.dtype, np.floating):
            assert np.isfinite(o).all(), f"{name}: non-finite output"

    # 2. analytic-vs-numeric gradient (float->float ops only).
    # List-input ops (concat/stack/...) count their ELEMENTS as inputs.
    def _float_tensors(obj):
        if isinstance(obj, Tensor):
            if np.issubdtype(np.asarray(obj._value).dtype, np.floating):
                yield obj
        elif isinstance(obj, (list, tuple)):
            for o in obj:
                yield from _float_tensors(o)

    def _sub(obj, old, new):
        if obj is old:
            return new
        if isinstance(obj, (list, tuple)):
            return type(obj)(_sub(o, old, new) for o in obj)
        return obj

    f_in = [t for a in args for t in _float_tensors(a)]
    grad_ok = (sp.get("grad", True) and f_in and outs
               and all(np.issubdtype(o.dtype, np.floating)
                       for o in outs))
    if grad_ok:
        x0 = f_in[0]
        base = np.asarray(x0._value).astype(np.float32)

        def run(arr):
            new_args = [_sub(a, x0, Tensor(jax.numpy.asarray(arr)))
                        for a in args]
            o = fn(*new_args, **kwargs)
            return o

        x = paddle.to_tensor(base, stop_gradient=False)
        new_args = [_sub(a, x0, x) for a in args]
        o = fn(*new_args, **kwargs)
        first = o[0] if isinstance(o, (tuple, list)) else o
        first.sum().backward()
        assert x.grad is not None, f"{name}: no grad"
        analytic = np.asarray(x.grad._value)
        # numeric on a FEW coordinates (full nd-sweep x 340 ops would
        # dominate the suite; 3 probes catch wrong-formula/transpose
        # errors, the common analytic-grad failure modes)
        eps = 1e-3
        flat_idx = [0, base.size // 2, base.size - 1]
        for fi in set(flat_idx):
            idx = np.unravel_index(fi, base.shape)
            hi, lo = base.copy(), base.copy()
            hi[idx] += eps
            lo[idx] -= eps

            def val(arr):
                o2 = run(arr)
                f2 = o2[0] if isinstance(o2, (tuple, list)) else o2
                return float(np.asarray(f2.sum()._value))

            num = (val(hi) - val(lo)) / (2 * eps)
            # atol floor: central differences of an f32 SUM carry
            # ~1e-2 cancellation noise (a true-zero gradient measures
            # as +-0.008 on a 100-element grid) — the probe targets
            # wrong-formula errors, not 5th-digit accuracy
            np.testing.assert_allclose(
                analytic[idx], num, rtol=5e-2, atol=1.5e-2,
                err_msg=f"{name}: analytic vs numeric grad at {idx}")

    # 3. bf16 tier: float inputs cast down must run and roughly match
    if sp.get("bf16", True) and name not in NO_BF16 and f_in and outs \
            and all(np.issubdtype(o.dtype, np.floating) for o in outs):
        import jax.numpy as jnp
        fids = {id(a) for a in f_in}     # identity, NOT Tensor __eq__

        def _bf(obj):
            if isinstance(obj, Tensor) and id(obj) in fids:
                return Tensor(obj._value.astype(jnp.bfloat16))
            if isinstance(obj, (list, tuple)):
                return type(obj)(_bf(o) for o in obj)
            return obj

        bf_args = [_bf(a) for a in args]
        try:
            ob = fn(*bf_args, **kwargs)
        except Exception as e:
            raise AssertionError(
                f"{name}: bf16 inputs rejected ({type(e).__name__}) — "
                "add to NO_BF16 with a reason if dtype-strict") from e
        for g, w in zip(_flat_np(ob), outs):
            np.testing.assert_allclose(
                np.asarray(g, np.float32), w, rtol=8e-2, atol=8e-2,
                err_msg=f"{name}: bf16 diverges from f32")
