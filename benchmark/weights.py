"""Seeded random weights, made on the device, one named leaf at a time.

The benchmark owns the weights: the program receives them through its
loader entry (``PagedLlamaDecoder.from_weight_loader``) or by assignment
to the model's parameters, and the reference makes the same leaves again
from the seed. Nothing the program derives from them (quantised values,
scales, fused matrices) is handed to the reference.

Leaf names are the serving loader's: ``embed`` [vocab, hidden], ``norm``,
``head`` [hidden, vocab], ``layers.{i}.{ln1,ln2,wq,wk,wv,wo,wg,wu,wd}``
with matrices stored [in, out].
"""
import zlib

import jax
import jax.numpy as jnp

LAYER_MATS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def model_seed(seed: int) -> int:
    """--seed is any whole number up to a little over 2**31; a PRNG key
    takes 31 bits."""
    return int(seed) % 2147483629


def leaf_shapes(model: dict):
    """[(name, shape)] of every leaf, in the serving loader's order."""
    h, it, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd
    out = [("embed", (v, h))]
    mats = {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h),
            "wg": (h, it), "wu": (h, it), "wd": (it, h)}
    for i in range(model["num_hidden_layers"]):
        out.append((f"layers.{i}.ln1", (h,)))
        out.append((f"layers.{i}.ln2", (h,)))
        out += [(f"layers.{i}.{k}", mats[k]) for k in LAYER_MATS]
    out += [("norm", (h,)), ("head", (h, v))]
    return out


def leaf_tag(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def leaf_traced(seed31, tag, shape, dtype, init_scale: float):
    """The leaf as pure ``jax.numpy``, for use inside a jitted function:
    ``seed31`` and ``tag`` may be traced. Norm gains (one dimension) are
    ones; every other leaf is N(0, init_scale) drawn in float32 from
    (seed, tag) and rounded once to ``dtype``."""
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    key = jax.random.fold_in(jax.random.PRNGKey(seed31), tag)
    return (jax.random.normal(key, shape, jnp.float32)
            * init_scale).astype(dtype)


_leaf_j = jax.jit(leaf_traced, static_argnums=(2, 3, 4))


def make_leaf(seed: int, name: str, shape, dtype, init_scale: float):
    """One leaf on the device, from (seed, name)."""
    return _leaf_j(model_seed(seed), leaf_tag(name),
                   tuple(int(s) for s in shape), jnp.dtype(dtype).name,
                   float(init_scale))
