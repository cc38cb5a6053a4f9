"""Varlen / segment-ids flash attention (fwd + bwd) and the group-aware
GQA backward.

Reference parity: flash_attn_unpadded
(/root/reference/python/paddle/nn/functional/flash_attention.py:302, CUDA
kernels paddle/phi/kernels/gpu/flash_attn_kernel.cu). The Pallas kernels
run in interpreter mode on the CPU test backend; the dense segmented
oracle (_sdpa_segmented_core) is the numerics reference, and gradients
are checked analytically against jax.grad through the oracle.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.flash_attention import (
    _sdpa_segmented_core, flash_attention_reference, flash_attn_varlen,
    segments_from_cu_seqlens)
from paddle_tpu.ops.pallas.flash_attention import (
    flash_attention_pallas, flash_attention_pallas_segmented)


def _rand_qkv(rng, b, sq, sk, h, hk, d, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(b, sq, h, d) * 0.5, dtype)
    k = jnp.asarray(rng.randn(b, sk, hk, d) * 0.5, dtype)
    v = jnp.asarray(rng.randn(b, sk, hk, d) * 0.5, dtype)
    return q, k, v


def _packed_segments(rng, b, s, n_docs):
    """Random doc boundaries per batch row -> segment ids [b, s]."""
    segs = []
    for _ in range(b):
        cuts = np.sort(rng.choice(np.arange(1, s), n_docs - 1,
                                  replace=False))
        seg = np.zeros(s, np.int32)
        for c in cuts:
            seg[c:] += 1
        segs.append(seg)
    return jnp.asarray(np.stack(segs))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("h,hk", [(4, 4), (4, 2)])
def test_segmented_kernel_matches_oracle(causal, h, hk):
    rng = np.random.RandomState(0)
    b, s, d = 2, 64, 8
    q, k, v = _rand_qkv(rng, b, s, s, h, hk, d)
    seg = _packed_segments(rng, b, s, 3)

    def pallas_fn(q, k, v):
        return flash_attention_pallas_segmented(q, k, v, seg, seg,
                                                causal, None, 32, 32)

    def oracle_fn(q, k, v):
        return _sdpa_segmented_core(q, k, v, seg, seg, causal,
                                    1.0 / np.sqrt(d))

    out_p = pallas_fn(q, k, v)
    out_o = oracle_fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_o),
                               atol=2e-5, rtol=2e-4)

    # gradient parity (analytic vs oracle autodiff)
    do = jnp.asarray(rng.randn(*out_o.shape), jnp.float32)
    gp = jax.grad(lambda *a: jnp.sum(pallas_fn(*a) * do), argnums=(0, 1, 2))(
        q, k, v)
    go = jax.grad(lambda *a: jnp.sum(oracle_fn(*a) * do), argnums=(0, 1, 2))(
        q, k, v)
    for a, b_ in zip(gp, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_group_aware_backward(causal):
    """The non-segmented kernel's new dk/dv path (group accumulation via
    grid revisiting, no jnp.repeat) must match the expanded-head oracle."""
    rng = np.random.RandomState(1)
    b, s, h, hk, d = 2, 64, 8, 2, 8
    q, k, v = _rand_qkv(rng, b, s, s, h, hk, d)

    def pallas_fn(q, k, v):
        return flash_attention_pallas(q, k, v, causal, None, 32, 32)

    def oracle_fn(q, k, v):
        return flash_attention_reference(q, k, v, causal=causal)

    np.testing.assert_allclose(np.asarray(pallas_fn(q, k, v)),
                               np.asarray(oracle_fn(q, k, v)),
                               atol=2e-5, rtol=2e-4)
    do = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    gp = jax.grad(lambda *a: jnp.sum(pallas_fn(*a) * do), argnums=(0, 1, 2))(
        q, k, v)
    go = jax.grad(lambda *a: jnp.sum(oracle_fn(*a) * do), argnums=(0, 1, 2))(
        q, k, v)
    for a, b_ in zip(gp, go):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-5, rtol=5e-4)


def test_fully_masked_rows_zero_not_nan():
    rng = np.random.RandomState(2)
    b, s, h, d = 1, 32, 2, 8
    q, k, v = _rand_qkv(rng, b, s, s, h, h, d)
    qseg = jnp.full((b, s), -1, jnp.int32)   # q attends nothing
    kseg = jnp.zeros((b, s), jnp.int32)
    out = flash_attention_pallas_segmented(q, k, v, qseg, kseg, False,
                                           None, 32, 32)
    assert np.all(np.asarray(out) == 0.0)
    g = jax.grad(lambda q: jnp.sum(flash_attention_pallas_segmented(
        q, k, v, qseg, kseg, False, None, 32, 32)))(q)
    assert np.all(np.isfinite(np.asarray(g)))
    assert np.all(np.asarray(g) == 0.0)


def test_segments_from_cu_seqlens():
    cu = jnp.asarray([0, 3, 5, 5, 9], jnp.int32)
    seg = segments_from_cu_seqlens(cu, 12)
    np.testing.assert_array_equal(
        np.asarray(seg), [0, 0, 0, 1, 1, 3, 3, 3, 3, -1, -1, -1])


def test_varlen_equals_per_doc_attention():
    """Packed 2-doc causal attention == each doc attended separately —
    the semantic point of the varlen API."""
    rng = np.random.RandomState(3)
    h, d = 2, 8
    l1, l2 = 24, 40
    total = l1 + l2
    q = jnp.asarray(rng.randn(total, h, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(total, h, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(total, h, d) * 0.5, jnp.float32)
    cu = jnp.asarray([0, l1, total], jnp.int32)
    out = flash_attn_varlen(q, k, v, cu, cu, causal=True)
    for sl in (slice(0, l1), slice(l1, total)):
        ref = flash_attention_reference(
            q[None, sl], k[None, sl], v[None, sl], causal=True)[0]
        np.testing.assert_allclose(np.asarray(out[sl]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-4)


def test_flash_attn_unpadded_functional_and_grad():
    """nn.functional.flash_attn_unpadded: packed pretrain-style step —
    forward + backward through the tape."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    rng = np.random.RandomState(4)
    h, d, total = 2, 8, 48
    cu = paddle.to_tensor(np.asarray([0, 20, 48], np.int32))
    q = paddle.to_tensor(np.asarray(rng.randn(total, h, d), np.float32))
    q.stop_gradient = False
    k = paddle.to_tensor(np.asarray(rng.randn(total, h, d), np.float32))
    k.stop_gradient = False
    v = paddle.to_tensor(np.asarray(rng.randn(total, h, d), np.float32))
    v.stop_gradient = False
    out, _ = F.flash_attn_unpadded(q, k, v, cu, cu, 48, 48,
                                   scale=1.0 / np.sqrt(d), causal=True)
    loss = (out * out).sum()
    loss.backward()
    for t in (q, k, v):
        ga = np.asarray(t.grad._value)
        assert np.all(np.isfinite(ga)) and np.abs(ga).max() > 0


class TestAttentionDropout:
    """Attention dropout is real on the dense path (applied to probs,
    upscale-in-train), not a silently-ignored argument."""

    def test_sdpa_dropout_changes_output(self):
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(2, 8, 2, 16).astype(np.float32))
        ev = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                            training=False)
        ev2 = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                             training=False)
        np.testing.assert_array_equal(np.asarray(ev._value),
                                      np.asarray(ev2._value))
        tr = F.scaled_dot_product_attention(q, q, q, dropout_p=0.5,
                                            training=True)
        assert not np.allclose(np.asarray(tr._value),
                               np.asarray(ev._value))

    def test_flash_attention_dropout_changes_output(self):
        import numpy as np
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(1)
        q = paddle.to_tensor(rng.randn(2, 8, 2, 16).astype(np.float32))
        ev, _ = F.flash_attention(q, q, q, dropout=0.3, training=False)
        tr, _ = F.flash_attention(q, q, q, dropout=0.3, training=True)
        assert not np.allclose(np.asarray(tr._value),
                               np.asarray(ev._value))

    def test_varlen_dropout_still_rejected(self):
        import numpy as np
        import pytest
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        q = paddle.to_tensor(np.zeros((8, 2, 16), np.float32))
        cu = paddle.to_tensor(np.array([0, 8], np.int32))
        with pytest.raises(NotImplementedError, match="dropout"):
            F.flash_attn_unpadded(q, q, q, cu, cu, 8, 8, scale=0.25,
                                  dropout=0.1)


def test_chunked_backward_matches_single_call():
    """The backward pass is one dq call and one dk/dv call that stream the
    other side sixteen blocks a step: at one, two and four chunks of a
    sequence (blocks of 8), causal or not, its gradients are those of
    the dense reference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    rng = np.random.RandomState(9)
    b, h, hk, d = 1, 4, 2, 8
    for s in (128, 256, 512):
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, hk, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, hk, d), jnp.float32)
        for causal in (True, False):
            def loss(fn):
                return lambda *a: jnp.sum(fn(*a) ** 2)
            got = jax.grad(loss(lambda *a: fa.flash_attention_pallas(
                *a, causal, None, 8, 8)), argnums=(0, 1, 2))(q, k, v)
            ref = jax.grad(loss(lambda *a: flash_attention_reference(
                *a, causal=causal)), argnums=(0, 1, 2))(q, k, v)
            for g, r in zip(got, ref):
                np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                           atol=2e-4, rtol=2e-4)
