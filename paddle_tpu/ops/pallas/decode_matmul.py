"""Weight-streaming matmul for decode-shaped activations (few rows).

Why: serving decode multiplies a tiny activation [b<=32, K] against
huge weights [K, N] — the op is pure weight-bandwidth, and XLA's stock
lowering picks compute-shaped tilings for an M=8 problem. (No cell of
the benchmark reaches this kernel: not measured on the chip.) This
kernel tiles N x K with the activation resident in VMEM, streams weight
tiles through the automatic Pallas pipeline, accumulates in an f32
VMEM scratch, and dequantizes int8 / nibble-packed int4 tiles on the
fly — so quantization's bandwidth win survives at any width.

Reference analog: the fused weight-only GEMV CUDA kernels behind the
serving path (/root/reference/paddle/phi/kernels/fusion/ +
python/paddle/incubate/nn/functional/block_multihead_attention.py:19
neighborhood); TPU-native form, shared by PagedLlamaDecoder/_mm.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import interpret as _interpret

__all__ = ["decode_matmul", "decode_matmul_supported"]

_MAX_ROWS = 32
# per-buffer VMEM budget for one weight tile (double-buffered by the
# pipeline; keep well under half of ~16 MB)
_TILE_BYTES = 2 * 1024 * 1024


def _pick_tile(dim: int, limit: int, cap: int = 2048,
               mult: int = 128) -> int:
    """Largest multiple of `mult` <= min(cap, limit) dividing dim (a
    fixed shortlist missed shapes like N=32000, whose best tile is
    1280 — the 256 fallback ran the head matmul at 1/4 bandwidth)."""
    top = min(cap, limit, dim)
    for t in range(top - top % mult, mult - 1, -mult):
        if dim % t == 0:
            return t
    return 0


def _tiles(K: int, N: int, w_bytes_per_elem: float):
    """(TK, TN) or None when the shape doesn't tile cleanly. int4's
    half-activation blocks are [b, TK/2], so TK must be a multiple of
    256 there (the lane rule applies to the HALVED tile)."""
    tn = _pick_tile(N, 1024)
    if not tn:
        return None
    # weight tile = TK x TN x bytes; bound by the VMEM budget
    tk_mult = 256 if w_bytes_per_elem == 0.5 else 128
    tk_limit = int(_TILE_BYTES / (tn * w_bytes_per_elem))
    tk = _pick_tile(K, max(tk_mult, tk_limit), mult=tk_mult)
    if not tk:
        return None
    return tk, tn


def decode_matmul_supported(x, w) -> bool:
    """True when (x, w) fits this kernel: TPU backend, 2-d x with few
    rows, and a cleanly tiling K x N (w dense, or (int8, scale) /
    (int4-packed, scale) pairs)."""
    if _interpret() or x.ndim != 2 or x.shape[0] > _MAX_ROWS:
        return False
    K = x.shape[1]
    if isinstance(w, tuple):
        wq, _ = w
        if wq.ndim != 2:
            return False
        if wq.shape[0] * 2 == K:      # int4 nibble-packed
            return _tiles(K, wq.shape[1], 0.5) is not None
        if wq.shape[0] != K:
            return False
        return _tiles(K, wq.shape[1], 1) is not None
    return (w.ndim == 2 and w.shape[0] == K
            and _tiles(K, w.shape[1], jnp.dtype(w.dtype).itemsize)
            is not None)


def _make_kernel(nk: int, kind: str, out_dtype):
    from jax.experimental import pallas as pl

    def kernel(*refs):
        # program_id(1) is the k step (grid = (n, k), k minor)
        ki = pl.program_id(1)
        if kind == "int4":
            # halves packing: packed row r encodes in-rows r (low
            # nibble) and r + K/2 (high) — the two activation views
            # are CONTIGUOUS halves, addressed by block specs over the
            # same x input (no host-side strided slicing; the old
            # even/odd layout burned 1.6 ms/step in slice fusions at
            # 8B). Mosaic can't shape-cast/stride in-kernel, which is
            # why the layout carries the split.
            xe_ref, xo_ref, w_ref, s_ref, o_ref, acc_ref = refs
        else:
            x_ref, w_ref, s_ref, o_ref, acc_ref = refs

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        if kind == "int4":
            # Mosaic has no int8 vector shifts: unpack via int32 with
            # branch-free sign extension of the low nibble
            w32 = w_ref[...].astype(jnp.int32)
            xe, xo = xe_ref[...], xo_ref[...]
            lo = (((w32 & 15) ^ 8) - 8).astype(xe.dtype)
            hi = (w32 >> 4).astype(xe.dtype)
            acc_ref[...] += (
                jax.lax.dot(xe, lo, preferred_element_type=jnp.float32)
                + jax.lax.dot(xo, hi,
                              preferred_element_type=jnp.float32))
        else:
            xb = x_ref[...]
            wb = w_ref[...]
            if kind == "int8":
                wb = wb.astype(xb.dtype)
            acc_ref[...] += jax.lax.dot(
                xb, wb, preferred_element_type=jnp.float32)

        @pl.when(ki == nk - 1)
        def _done():
            acc = acc_ref[...]
            if kind in ("int8", "int4"):
                acc = acc * s_ref[...].astype(jnp.float32)
            o_ref[...] = acc.astype(out_dtype)

    return kernel


def decode_matmul(x, w):
    """x [b, K] @ w -> [b, N]; w is dense [K, N], (int8 [K, N], scale
    [N]) or (int4-packed [K/2, N], scale [N]). int4 packing MUST be
    the HALVES layout (_quantize_w4_halves: packed row r = in-rows r
    and r + K/2); the interleaved even/odd layout is not detectable
    from the tuple and would silently produce wrong results. Caller
    must have checked decode_matmul_supported."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, K = x.shape
    if isinstance(w, tuple):
        wq, scale = w
        if wq.shape[0] * 2 == K:
            kind, wbytes = "int4", 0.5
        else:
            kind, wbytes = "int8", 1
        N = wq.shape[1]
    else:
        wq, scale = w, jnp.ones((w.shape[1],), jnp.float32)
        kind, wbytes = "dense", jnp.dtype(w.dtype).itemsize
        N = w.shape[1]
    tk, tn = _tiles(K, N, wbytes)
    nk, nn = K // tk, N // tn
    wtk = tk // 2 if kind == "int4" else tk

    kernel = _make_kernel(nk, kind, x.dtype)
    if kind == "int4":
        # the same x feeds two specs: k-th block of the FIRST half
        # (low nibbles) and of the SECOND half (block index k + nk)
        ins = (x, x, wq, scale.reshape(1, N))
        in_specs = [
            pl.BlockSpec((b, tk // 2), lambda j, k: (0, k)),
            pl.BlockSpec((b, tk // 2), lambda j, k, _nk=nk: (0, k + _nk)),
            pl.BlockSpec((wtk, tn), lambda j, k: (k, j)),
            pl.BlockSpec((1, tn), lambda j, k: (0, j)),
        ]
    else:
        ins = (x, wq, scale.reshape(1, N))
        in_specs = [
            pl.BlockSpec((b, tk), lambda j, k: (0, k)),
            pl.BlockSpec((wtk, tn), lambda j, k: (k, j)),
            pl.BlockSpec((1, tn), lambda j, k: (0, j)),
        ]
    return pl.pallas_call(
        kernel,
        grid=(nn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, tn), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((b, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((b, tn), jnp.float32)],
        name="decode_matmul",
    )(*ins)
