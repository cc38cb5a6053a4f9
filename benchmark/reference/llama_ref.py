"""Plain float32 reference of the Llama equations (RMSNorm, rotary
embedding in the half-split convention, grouped-query causal attention,
SwiGLU, untied head), in straightforward ``jax.numpy``.

Imports nothing of the program and takes nothing the program made: the
weights come again from the seed (``benchmark.weights``, by the family's
leaf list), and where the
configuration states weight-only int8 the reference quantises them itself
(per-output-channel absmax, the published scheme) and computes in float32
with the dequantised values. Every matmul runs under
``default_matmul_precision("highest")``: a TPU otherwise multiplies float32
in bfloat16 passes.

``precision`` selects the control, the nearest precision below the stated
one: ``"stated"`` is float32 arithmetic on the stated weights; ``"lower"``
stores matmul weights in the next format down (int4 for int8, fp8-e4m3 for
bfloat16) and rounds every matmul input to bfloat16 (serving) or fp8
(training).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from ..families import llama as family

F32 = jnp.float32
HI = "highest"


# -- weight formats -----------------------------------------------------------

def quantise_absmax(w, levels: int):
    """Per-output-channel symmetric absmax: w [in, out] float32 ->
    (integers as float32, scale [out]). levels 127 is int8, 7 is int4
    (whose grid also has -8)."""
    scale = jnp.max(jnp.abs(w), axis=0) / levels
    scale = jnp.where(scale == 0, 1.0, scale)
    lo = -levels - (1 if levels == 7 else 0)
    return jnp.clip(jnp.round(w / scale[None, :]), lo, levels), scale


def stored_weight(w, fmt: str):
    """The float32 value of a matmul weight as the format ``fmt`` keeps
    it."""
    w = w.astype(F32)
    if fmt == "float32":
        return w
    if fmt == "bfloat16":       # a no-op on a leaf that was made in bf16
        return w.astype(jnp.bfloat16).astype(F32)
    if fmt == "int8":
        q, s = quantise_absmax(w, 127)
        return q * s[None, :]
    if fmt == "int4":
        q, s = quantise_absmax(w, 7)
        return q * s[None, :]
    if fmt == "fp8":
        s = jnp.max(jnp.abs(w), axis=0) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown weight format {fmt!r}")


LOWER = {"int8": "int4", "bfloat16": "fp8", "float32": "bfloat16"}


def act_round(x, fmt):
    """Round a matmul input as the control's activation format does."""
    if fmt is None:
        return x
    if fmt == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    if fmt == "fp8":
        s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
        s = jnp.where(s == 0, 1.0, s)
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown activation format {fmt!r}")


# -- the equations ------------------------------------------------------------

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """x [s, heads, d], positions 0..s-1, half-split rotation."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def mm(x, w, act_fmt=None):
    return jnp.matmul(act_round(x, act_fmt), w, precision=HI)


def attention(q, k, v, q_block: int = 1024):
    """Causal grouped-query attention of one sequence: q [s, nh, d],
    k/v [s, kvh, d]; query rows in blocks so that the scores fit."""
    s, nh, d = q.shape
    kvh = k.shape[1]
    qg = q.reshape(s, kvh, nh // kvh, d)
    outs = []
    for a in range(0, s, q_block):
        b = min(s, a + q_block)
        sc = jnp.einsum("qkgd,skd->kgqs", qg[a:b], k[:b],
                        precision=HI) / np.sqrt(d)
        mask = jnp.arange(b)[None, :] <= jnp.arange(a, b)[:, None]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("kgqs,skd->qkgd", p, v[:b], precision=HI))
    return jnp.concatenate(outs, 0).reshape(s, nh * d)


def layer(x, lw, model, act_fmt=None):
    """One decoder block on one sequence x [s, hidden]."""
    nh, kvh, d = model["num_attention_heads"], \
        model["num_key_value_heads"], model["head_dim"]
    eps, s = model["rms_norm_eps"], x.shape[0]
    hn = rms_norm(x, lw["ln1"], eps)
    q = rope(mm(hn, lw["wq"], act_fmt).reshape(s, nh, d),
             model["rope_theta"])
    k = rope(mm(hn, lw["wk"], act_fmt).reshape(s, kvh, d),
             model["rope_theta"])
    v = mm(hn, lw["wv"], act_fmt).reshape(s, kvh, d)
    x = x + mm(attention(q, k, v), lw["wo"], act_fmt)
    hn = rms_norm(x, lw["ln2"], eps)
    gate = jax.nn.silu(mm(hn, lw["wg"], act_fmt)) * mm(hn, lw["wu"], act_fmt)
    return x + mm(gate, lw["wd"], act_fmt)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer_weights_j(seed31, tags, shapes, dtype, init_scale, fmt):
    out = {}
    for j, (k, shape) in enumerate(shapes):
        leaf = W.leaf_traced(seed31, tags[j], shape, dtype,
                             init_scale).astype(F32)
        out[k] = stored_weight(leaf, fmt) if k in family.LAYER_MATS else leaf
    return out


def layer_weights(cfg: dict, seed: int, i: int, fmt: str):
    """Layer i's leaves from the seed, matrices as ``fmt`` stores them
    (one compiled function makes every layer)."""
    m = cfg["model"]
    all_shapes = dict(family.leaf_shapes(m))
    keys = ("ln1", "ln2") + family.LAYER_MATS
    shapes = tuple((k, tuple(all_shapes[f"layers.{i}.{k}"])) for k in keys)
    tags = np.asarray([W.leaf_tag(f"layers.{i}.{k}") for k in keys],
                      np.int32)
    return _layer_weights_j(W.model_seed(seed), tags, shapes,
                            m["torch_dtype"], float(cfg["init_scale"]), fmt)


def top_leaf(cfg: dict, seed: int, name: str, fmt: str = None):
    leaf = W.Leaves(family, cfg, seed).make(name).astype(F32)
    return stored_weight(leaf, fmt) if fmt else leaf


# -- serving: logits of given sequences, layer by layer -----------------------

def _bucket(n: int, step: int = 1024) -> int:
    return -(-n // step) * step


def sequence_logits(cfg: dict, seed: int, seqs, positions,
                    precision: str = "stated"):
    """Float32 logits of the reference at chosen positions.

    seqs: token id arrays (a prompt followed by its served tokens);
    positions: for each, the indices whose next-token logits are wanted.
    Weights are made one layer at a time and every sequence goes through
    the layer before the next is made, so the whole model is never held.
    Returns one [len(positions[i]), vocab] array per sequence."""
    m = cfg["model"]
    fmt = cfg["precision"]["weights"]
    act_fmt = None
    if precision == "lower":
        fmt, act_fmt = LOWER[fmt], "bfloat16"
    elif precision != "stated":
        raise ValueError(precision)
    step = jax.jit(functools.partial(layer, model=m, act_fmt=act_fmt))
    with jax.default_matmul_precision(HI):
        embed = top_leaf(cfg, seed, "embed")
        xs = []
        for ids in seqs:
            ids = np.asarray(ids, np.int32)
            pad = np.zeros(_bucket(len(ids)), np.int32)
            pad[:len(ids)] = ids      # padding lies after, causally unseen
            xs.append(jnp.take(embed, jnp.asarray(pad), axis=0))
        del embed
        for i in range(m["num_hidden_layers"]):
            lw = layer_weights(cfg, seed, i, fmt)
            xs = [step(x, lw) for x in xs]
            del lw
        norm = top_leaf(cfg, seed, "norm")
        head = top_leaf(cfg, seed, "head", fmt)
        out = []
        for x, pos in zip(xs, positions):
            # positions padded to a few fixed lengths, so that the head
            # is a handful of compiled programs and not one per request
            pad = np.zeros(_bucket(len(pos), 128), np.int32)
            pad[:len(pos)] = np.asarray(pos, np.int32)
            out.append(_head_logits(x, jnp.asarray(pad), norm, head,
                                    m["rms_norm_eps"], act_fmt)[:len(pos)])
    return out


@functools.partial(jax.jit, static_argnums=(4, 5))
def _head_logits(x, pos, norm, head, eps, act_fmt):
    return mm(rms_norm(x[pos], norm, eps), head, act_fmt)


# -- training: loss, gradients and AdamW --------------------------------------

def train_params(cfg: dict, seed: int, precision: str = "stated"):
    """Every leaf in float32, as the trainer's parameters start."""
    fmt = LOWER[cfg["precision"]["weights"]] if precision == "lower" \
        else None
    seeded = W.Leaves(family, cfg, seed)
    out = {}
    for name, shape in seeded.shapes.items():
        leaf = seeded.make(name).astype(F32)
        is_mat = len(shape) == 2 and name != "embed"
        out[name] = stored_weight(leaf, fmt) if (fmt and is_mat) else leaf
    return out


def row_loss_sum(params, ids, model, act_fmt=None):
    """Summed next-token cross entropy of one row ids [s]."""
    x = jnp.take(params["embed"], ids, axis=0)
    for i in range(model["num_hidden_layers"]):
        lw = {k: params[f"layers.{i}.{k}"]
              for k in ("ln1", "ln2") + family.LAYER_MATS}
        x = jax.checkpoint(functools.partial(
            layer, model=model, act_fmt=act_fmt))(x, lw)
    h = rms_norm(x, params["norm"], model["rms_norm_eps"])

    def ce(hh, tgt):
        logits = mm(hh, params["head"], act_fmt)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0])
    return jax.checkpoint(ce)(h[:-1], ids[1:])


def loss_and_grads(params, batch, cfg: dict, precision: str = "stated",
                   rows=None):
    """Mean shifted causal-LM loss over the batch and its gradients, one
    row at a time so that a row's activations are all that is held.
    ``rows`` limits the mean to those rows (a planted fault in tests)."""
    model = cfg["model"]
    act_fmt = "fp8" if precision == "lower" else None
    batch = np.asarray(batch, np.int32)
    rows = list(range(batch.shape[0])) if rows is None else list(rows)
    denom = len(rows) * (batch.shape[1] - 1)
    f = jax.jit(jax.value_and_grad(functools.partial(
        row_loss_sum, model=model, act_fmt=act_fmt)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    loss, grads = 0.0, None
    with jax.default_matmul_precision(HI):
        for r in rows:
            l_r, g_r = f(params, jnp.asarray(batch[r]))
            loss += float(l_r)
            grads = g_r if grads is None else add(grads, g_r)
            del g_r
        scale = jax.jit(lambda g: jax.tree.map(lambda a: a / denom, g),
                        donate_argnums=(0,))
        grads = scale(grads)
    return loss / denom, grads
