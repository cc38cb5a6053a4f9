"""paddle.distributed.rpc (subprocess pattern per SURVEY §4), device
namespace, regularizer tests."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import native
from paddle_tpu.distributed import spawn


def _sq(x):
    return x * x


def _rpc_worker(port):
    from paddle_tpu.distributed import rpc
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    rpc.init_rpc(f"w{rank}", master_endpoint=f"127.0.0.1:{port}")
    if rank == 0:
        assert rpc.rpc_sync("w1", _sq, args=(7,)) == 49
        fut = rpc.rpc_async("w1", _sq, args=(3,))
        assert fut.wait() == 9
        names = {i.name for i in rpc.get_all_worker_infos()}
        assert names == {"w0", "w1"}
        with pytest.raises(RuntimeError, match="remotely"):
            rpc.rpc_sync("w1", _boom)
    rpc.shutdown()


def _boom():
    raise ValueError("kaput")


@pytest.mark.skipif(not native.available(), reason="needs native store")
def test_rpc_two_workers():
    from paddle_tpu.distributed.launch.context import free_port
    spawn(_rpc_worker, args=(free_port(),), nprocs=2)


class TestDeviceNamespace:
    def test_introspection(self):
        assert paddle.device.get_device_count() >= 1
        types = paddle.device.get_all_device_type()
        assert types and all(isinstance(t, str) for t in types)
        assert len(paddle.device.get_available_device()) >= 1
        assert not paddle.device.is_compiled_with_cuda()
        assert paddle.device.cuda.device_count() == 0

    def test_set_device_without_accelerator_raises(self):
        """Asking for an accelerator and finding none is an error, not
        a CPU device under the accelerator's name."""
        before = paddle.get_device()
        for name in ("tpu", "gpu:0", "accelerator"):
            with pytest.raises(RuntimeError, match="no accelerator"):
                paddle.set_device(name)
        assert paddle.get_device() == before
        assert paddle.set_device("cpu").platform == "cpu"

    def test_stream_event_noop_api(self):
        s = paddle.device.current_stream()
        e = s.record_event()
        assert e.query()
        e.synchronize()
        s.synchronize()
        paddle.device.synchronize()


class TestRegularizer:
    def test_l1_l2_grad_terms(self):
        import jax.numpy as jnp
        from paddle_tpu.regularizer import L1Decay, L2Decay
        p = jnp.asarray([2.0, -3.0])
        g = jnp.zeros(2)
        np.testing.assert_allclose(
            np.asarray(L2Decay(0.1).apply_to_grad(p, g)), [0.2, -0.3])
        np.testing.assert_allclose(
            np.asarray(L1Decay(0.5).apply_to_grad(p, g)), [0.5, -0.5])
