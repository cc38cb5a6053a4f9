"""Seeded random weights, made on the device, one named leaf at a time.

The benchmark owns the weights: the program receives them through its
loader entry or by assignment to the model's parameters, and the
reference makes the same leaves again from the seed. Nothing the program
derives from them (quantised values, scales, fused matrices) is handed to
the reference. Which leaves a configuration has, by what names and of
what kind, is its family's to say (``benchmark/families``).
"""
import zlib

import jax
import jax.numpy as jnp

KINDS = ("normal", "ones", "zeros")


def model_seed(seed: int) -> int:
    """--seed is any whole number up to a little over 2**31; a PRNG key
    takes 31 bits."""
    return int(seed) % 2147483629


def leaf_tag(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf_traced(seed31, tag, shape, dtype, init_scale: float, kind=None):
    """The leaf as pure ``jax.numpy``, for use inside a jitted function:
    ``seed31`` and ``tag`` may be traced. ``ones`` and ``zeros`` are
    that; ``normal`` is N(0, init_scale) drawn in float32 from (seed,
    tag) and rounded once to ``dtype``. With no kind given a leaf of one
    dimension (a norm's gain) is ones and every other normal."""
    kind = kind or ("ones" if len(shape) == 1 else "normal")
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind != "normal":
        raise ValueError(f"unknown leaf kind {kind!r}; there are: "
                         f"{', '.join(KINDS)}")
    key = jax.random.fold_in(jax.random.PRNGKey(seed31), tag)
    return (jax.random.normal(key, shape, jnp.float32)
            * init_scale).astype(dtype)


_leaf_j = jax.jit(leaf_traced, static_argnums=(2, 3, 4, 5))


def make_leaf(seed: int, name: str, shape, dtype, init_scale: float,
              kind=None):
    """One leaf on the device, from (seed, name)."""
    return _leaf_j(model_seed(seed), leaf_tag(name),
                   tuple(int(s) for s in shape), jnp.dtype(dtype).name,
                   float(init_scale), kind)


class Leaves:
    """A configuration's leaves as its family lists them: ``shapes``
    {name: shape} in the family's order, ``kinds`` {name: kind or None},
    and ``make(name)``, the leaf of this seed on the device."""

    def __init__(self, family, cfg: dict, seed: int):
        self.cfg, self.seed = cfg, seed
        self.shapes, self.kinds = {}, {}
        for name, shape, *kind in family.leaf_shapes(cfg["model"]):
            self.shapes[name] = tuple(shape)
            self.kinds[name] = kind[0] if kind else None

    def make(self, name: str, shape=None):
        """``shape`` is for a loader that states the shape it wants."""
        return make_leaf(self.seed, name, shape or self.shapes[name],
                         self.cfg["model"]["torch_dtype"],
                         self.cfg["init_scale"], self.kinds[name])
