"""Pallas TPU kernels (the reference's CUDA fusion inventory, TPU-native:
/root/reference/paddle/phi/kernels/fusion/ + third_party/flashattn)."""
import jax


def interpret() -> bool:
    """Pallas interpreter mode off the TPU backend (CPU tests and the
    numerics oracle); on the chip the kernels compile through Mosaic."""
    return jax.default_backend() != "tpu"
