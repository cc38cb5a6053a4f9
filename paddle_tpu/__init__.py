"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability set, built on JAX/XLA/Pallas/pjit.

Top-level namespace mirrors `import paddle` (/root/reference/python/paddle/
__init__.py): tensor ops, nn, optimizer, amp, io, jit, distributed, vision.
"""
from __future__ import annotations

__version__ = "0.1.0"

from .framework import (
    Tensor, Parameter, no_grad, enable_grad, is_grad_enabled, to_tensor,
    set_device, get_device, seed, get_rng_state, set_rng_state,
    get_default_dtype, set_default_dtype,
)
from .framework.dtype import (  # dtype aliases: paddle.float32 etc.
    bool_ as bool,  # noqa: A001
    uint8, int8, int16, int32, int64, float16, bfloat16, float32, float64,
    complex64, complex128, float8_e4m3fn, float8_e5m2,
)

from .tensor import *  # noqa: F401,F403 — op namespace at top level, like paddle
from . import tensor  # noqa: F401
# the star import above binds `linalg` to tensor.linalg (submodule name
# leak), and `from . import linalg` would short-circuit on that existing
# attribute — import the real namespace module explicitly
import importlib as _importlib
linalg = _importlib.import_module(".linalg", __name__)
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import autograd  # noqa: F401
from . import vision  # noqa: F401
from . import distributed  # noqa: F401
from . import ops  # noqa: F401
from . import utils  # noqa: F401
from . import metric  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import hapi  # noqa: F401
from . import profiler  # noqa: F401
from . import static  # noqa: F401
from . import incubate  # noqa: F401
from . import sparse  # noqa: F401
from . import geometric  # noqa: F401
from . import audio  # noqa: F401
from . import text  # noqa: F401
from . import quantization  # noqa: F401
from . import inference  # noqa: F401
from . import decomposition  # noqa: F401
from . import cost_model  # noqa: F401
from . import onnx  # noqa: F401
from . import device  # noqa: F401
from . import regularizer  # noqa: F401
from .hapi import callbacks  # noqa: F401  — paddle.callbacks alias
from .hapi import Model, summary, flops  # noqa: F401
from .framework.io import save, load  # noqa: F401

from .jit import to_static  # noqa: F401
from .autograd import grad  # noqa: F401

# paddle.DataParallel-style alias
from .distributed.parallel import DataParallel  # noqa: F401


def device_count() -> int:
    import jax
    return jax.device_count()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


def disable_static(place=None):
    from . import static as _static
    _static.disable_static()
    return None


def enable_static():
    from . import static as _static
    _static.enable_static()


def in_dynamic_mode() -> bool:
    from .static.program import in_static_mode
    return not in_static_mode()

# --- top-level long tail (reference python/paddle/__init__.py) -------------


class CPUPlace:
    """Device place objects (reference CPUPlace/CUDAPlace/...); device
    selection on TPU goes through set_device — these exist so
    place-typed reference code constructs."""

    def __repr__(self):
        return "Place(cpu)"


class CUDAPlace:
    def __init__(self, device_id=0):
        self.device_id = device_id

    def __repr__(self):
        return f"Place(accelerator:{self.device_id})"


class CUDAPinnedPlace:
    def __repr__(self):
        return "Place(pinned)"


class LazyGuard:
    """Reference LazyGuard defers parameter initialization; paddle_tpu
    initializes eagerly (cheap on host, arrays are lazy on device
    anyway) — the guard is a transparent context."""

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


from .nn import ParamAttr  # noqa: F401,E402


def batch(reader, batch_size, drop_last=False):
    """Reference paddle.batch: wrap a sample reader into a batch
    reader."""
    def batch_reader():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batch_reader


def check_shape(x):
    from .static.program import in_static_mode
    return list(x.shape)


def disable_signal_handler():
    """Reference disables paddle's C++ signal handlers; there are none
    here (pure-Python runtime) — accepted no-op by construction."""


dtype = _np_mod = None
from .framework import dtype as _dtype_mod  # noqa: E402


class dtype:  # noqa: F811 — paddle.dtype(type) constructor parity
    def __new__(cls, d):
        return _dtype_mod.convert_dtype(d)


def finfo(d):
    import numpy as _np
    return _np.finfo(_dtype_mod.convert_dtype(d))


def iinfo(d):
    import numpy as _np
    return _np.iinfo(_dtype_mod.convert_dtype(d))


def get_cuda_rng_state():
    """Accelerator RNG state (the reference's 'cuda' = the device)."""
    return get_rng_state()


def set_cuda_rng_state(state):
    return set_rng_state(state)


def get_flags(flags):
    from .utils.flags import FLAGS
    if isinstance(flags, str):
        flags = [flags]
    return {f: getattr(FLAGS, f.replace("FLAGS_", ""), None)
            for f in flags}


def set_flags(flags):
    from .utils.flags import FLAGS
    for k, v in flags.items():
        setattr(FLAGS, k.replace("FLAGS_", ""), v)


def set_grad_enabled(mode: bool):
    from .framework.core import _grad_state

    class _Guard:
        def __init__(self):
            self._prev = _grad_state.enabled
            _grad_state.enabled = bool(mode)

        def __enter__(self):
            return self

        def __exit__(self, *a):
            _grad_state.enabled = self._prev
            return False

    return _Guard()


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def pdist(x, p=2.0, name=None):
    """Pairwise distances, condensed form (reference paddle.pdist)."""
    from . import tensor as _T
    import jax.numpy as _jnp
    from .framework.core import apply as _apply

    def f(a):
        nr = a.shape[0]
        d = _jnp.linalg.norm(a[:, None] - a[None, :] + 0.0, ord=p,
                             axis=-1)
        iu = _jnp.triu_indices(nr, k=1)
        return d[iu]
    return _apply("pdist", f, x)


def tolist(x):
    """Free-function form of Tensor.tolist (reference paddle.tolist) —
    does NOT re-register the method (that would shadow the original)."""
    import numpy as _np
    return _np.asarray(x._value if hasattr(x, "_value") else x).tolist()


# erf_/expm1_/square_ come from tensor._INPLACE_NAMES (star-exported)
