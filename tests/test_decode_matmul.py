"""Weight-streaming decode matmul kernel (ops/pallas/decode_matmul).

The kernel is TPU-only (its value is HBM streaming; its speed is not
measured on the chip by any cell of the benchmark; that it compiles for
the chip is tests/test_chip_compile.py's); here: the tile chooser's invariants on the real model shapes, the
support gate off-TPU, and a skip-on-CPU correctness check against the
plain dequant matmul. Reference analog: the weight-only GEMV CUDA
kernels behind the serving path (paddle/phi/kernels/fusion/).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.decode_matmul import (_tiles,
                                                 decode_matmul,
                                                 decode_matmul_supported)

requires_tpu = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="streaming kernel only engages on the chip")


def test_tile_chooser_covers_model_shapes():
    # (K, N) pairs from llama_small / llama_3_8b layers and heads
    shapes = [(2048, 2048), (2048, 1024), (2048, 5632), (5632, 2048),
              (2048, 32000), (4096, 4096), (4096, 1024), (4096, 14336),
              (14336, 4096), (4096, 128256)]
    for K, N in shapes:
        for wbytes in (2, 1, 0.5):
            t = _tiles(K, N, wbytes)
            assert t is not None, (K, N, wbytes)
            tk, tn = t
            assert K % tk == 0 and N % tn == 0
            assert tn % 128 == 0
            # int4 splits the activation tile in half: lane rule needs
            # tk/2 to stay a multiple of 128
            assert tk % (256 if wbytes == 0.5 else 128) == 0
            # weight tile respects the VMEM budget
            assert tk * tn * wbytes <= 2 * 1024 * 1024
    # the N=32000 head picks a wide tile, not the 256 fallback that
    # ran at 1/4 bandwidth
    assert _tiles(2048, 32000, 2)[1] >= 640


def test_supported_gate():
    x = jnp.ones((8, 2048), jnp.bfloat16)
    w = jnp.ones((2048, 1024), jnp.bfloat16)
    if jax.default_backend() != "tpu":
        assert not decode_matmul_supported(x, w)
        return
    assert decode_matmul_supported(x, w)
    assert not decode_matmul_supported(jnp.ones((64, 2048),
                                                jnp.bfloat16), w)
    assert not decode_matmul_supported(x, jnp.ones((999, 1024),
                                                   jnp.bfloat16))


@requires_tpu
def test_kernel_matches_dequant_matmul():
    rng = np.random.RandomState(0)
    b, K, N = 8, 2048, 5632
    x = jnp.asarray(rng.randn(b, K).astype(np.float32) * 0.1) \
        .astype(jnp.bfloat16)
    wf = rng.randn(K, N).astype(np.float32) * 0.02
    for kind in ("dense", "int8", "int4"):
        if kind == "dense":
            w = jnp.asarray(wf).astype(jnp.bfloat16)
            ref = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
        elif kind == "int8":
            s = (np.abs(wf).max(0) / 127).astype(np.float32)
            q = np.clip(np.round(wf / s), -127, 127).astype(np.int8)
            w = (jnp.asarray(q), jnp.asarray(s))
            ref = (np.asarray(x, np.float32) @ q.astype(np.float32)) * s
        else:
            s = (np.abs(wf).max(0) / 7).astype(np.float32)
            q = np.clip(np.round(wf / s), -8, 7).astype(np.int8)
            half = K // 2
            packed = ((q[:half] & 0x0F)
                      | ((q[half:] & 0x0F) << 4)).astype(np.int8)
            w = (jnp.asarray(packed), jnp.asarray(s))
            ref = (np.asarray(x, np.float32) @ q.astype(np.float32)) * s
        got = np.asarray(jax.jit(decode_matmul)(x, w), np.float32)
        rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 0.02, (kind, rel)
