"""Plain float32 reference of the Laguna language model's equations as
``benchmark/configs/laguna_*.json`` states them, in straightforward
``jax.numpy``. Layer ``i`` on its input ``x``: ``h = x +
Attention(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``.

Attention: ``num_attention_heads_per_layer[i]`` query heads over the key
heads (grouped-query attention) under an explicit mask, causal and, on a
``sliding_attention`` layer, banded (query t sees the keys s with
``t - sliding_window < s <= t``); q and k under the rotary embedding of
``rope_parameters[layer_types[i]]`` over their first
``partial_rotary_factor * head_dim`` dimensions (half-split pairs inside
them, the rest passed through): ``default`` at its theta, or YaRN, whose
inverse frequencies are written out here from the formula (``yarn``)
and whose cos and sin are multiplied by its ``attention_factor``; each
head's output times ``sigmoid(RMSNorm(x) W_g)`` at that head before the
output projection. FFN: the SwiGLU MLP on a ``dense`` layer; on a
``sparse`` one softmax over all the experts in float32, the largest
``num_experts_per_tok`` divided by their sum and multiplied by
``moe_routed_scaling_factor``, of the SwiGLU experts only the share this
chip holds, each on every token under a mask, plus the shared SwiGLU
expert on every token. A last RMSNorm and the untied head.

Departures from the published description, each also in the
configuration file: what the absent experts would add is left out, as in
the program; the vocabulary is this chip's slice; no auxiliary loss.

Imports nothing of the program: the leaves come again from the seed by
the family's list, every matmul runs at ``highest``, there is no kernel.
One sequence at a time, attention's queries in blocks under
``jax.checkpoint`` (``smallthinker_ref.banded_attention``; a full layer
has a window of ``NO_WINDOW`` keys), the head and the cross entropy in
blocks of positions likewise; consecutive layers of one kind are stacked
and scanned, so that each kind is compiled once, and every step of a
check runs the one compiled program.

``precision="lower"`` is the control: matrices stored in fp8-e4m3 (per
output channel, the embedding's gather apart) and every matmul input
rounded to fp8, the nearest precision below the stated bfloat16.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from ..families import lm_laguna as family
from .keye_vl2_ref import F32, HI, act_round, mm, rms_norm, stored_fp8
from .smallthinker_ref import NO_WINDOW, banded_attention


def yarn(dim, theta, factor, original, beta_fast, beta_slow):
    """YaRN's inverse frequencies of ``dim`` rotated dimensions: the
    correction dimension of r rotations over the original context is
    dim ln(original / (2 pi r)) / (2 ln theta); below the floor of
    beta_fast's a pair keeps theta^(-2i/dim), above the ceiling of
    beta_slow's it takes that over ``factor``, and between them the
    linear ramp blends the two."""
    def corr(r):
        return dim * math.log(original / (2 * math.pi * r)) \
            / (2 * math.log(theta))
    lo, hi = max(math.floor(corr(beta_fast)), 0), \
        min(math.ceil(corr(beta_slow)), dim - 1)
    if lo == hi:
        hi += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - lo) / (hi - lo), 0, 1)     # 1: interpolated
    own = theta ** (-2 * i / dim)
    return own * (1 - ramp) + own / factor * ramp


def rotary(x, rope, d):
    """x [s, heads, d] under the layer's rotary embedding."""
    dim = int(d * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    if rope["rope_type"] == "yarn":
        inv = yarn(dim, theta, float(rope["factor"]),
                   float(rope["original_max_position_embeddings"]),
                   float(rope["beta_fast"]), float(rope["beta_slow"]))
        scale = float(rope["attention_factor"])
    else:
        inv, scale = theta ** (-np.arange(0, dim, 2) / dim), 1.0
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] \
        * jnp.asarray(inv, F32)[None, :]
    cos = scale * jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = scale * jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    turn, rest = x[..., :dim], x[..., dim:]
    a, b = jnp.split(turn, 2, axis=-1)
    return jnp.concatenate(
        [turn * cos + jnp.concatenate([-b, a], -1) * sin, rest], -1)


def attention(x, lw, model, kind, act_fmt=None):
    """One layer's gated attention on the normed x [s, hidden]."""
    nh, kvh, d = lw["wg"].shape[-1], model["num_key_value_heads"], \
        model["head_dim"]
    s, rope = x.shape[0], model["rope_parameters"][kind]
    q = rotary(mm(x, lw["wq"], act_fmt).reshape(s, nh, d), rope, d)
    k = rotary(mm(x, lw["wk"], act_fmt).reshape(s, kvh, d), rope, d)
    v = mm(x, lw["wv"], act_fmt).reshape(s, kvh, d)
    window = model["sliding_window"] if kind == "sliding_attention" \
        else NO_WINDOW
    o = banded_attention(q, k, v, window).reshape(s, nh, d)
    gate = jax.nn.sigmoid(mm(x, lw["wg"], act_fmt))
    return mm((o * gate[..., None]).reshape(s, nh * d), lw["wo"], act_fmt)


def swiglu(x, w1, w3, w2, act_fmt=None):
    return mm(jax.nn.silu(mm(x, w1, act_fmt)) * mm(x, w3, act_fmt), w2,
              act_fmt)


def route(x, lw, model, act_fmt=None):
    """(top_i [s, k], gates [s, k]): softmax over every expert, the
    largest chosen, divided by their sum, scaled."""
    probs = jax.nn.softmax(mm(x, lw["wr"], act_fmt), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, model["num_experts_per_tok"])
    if model["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_i, top_p * float(model["moe_routed_scaling_factor"])


def held_experts(x, lw, model, act_fmt=None):
    """The held experts' part of the expert layer's output for x
    [s, hidden]: each held expert on every token, weighted by the token's
    gate for it (nought where it was not chosen)."""
    first = model["expert_share"][0] * model["num_experts"]
    top_i, gates = route(x, lw, model, act_fmt)
    # rounded once, not once an expert: the scan would keep every copy
    # for its backward pass
    xr = act_round(x, act_fmt)

    @jax.checkpoint
    def one(ew):
        wg, wu, wd, e = ew
        gate = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), -1)
        return gate[:, None] * mm(jax.nn.silu(mm(xr, wg)) * mm(xr, wu), wd,
                                  act_fmt)

    y, _ = jax.lax.scan(lambda y, ew: (y + one(ew), None), jnp.zeros_like(x),
                        (lw["eg"], lw["eu"], lw["ed"],
                         jnp.arange(model["num_experts"])))
    return y


def feed_forward(x, lw, model, dense, act_fmt=None):
    if dense:
        return swiglu(x, lw["w1"], lw["w3"], lw["w2"], act_fmt)
    return held_experts(x, lw, model, act_fmt) \
        + swiglu(x, lw["sg"], lw["su"], lw["sd"], act_fmt)


def layer(x, lw, model, kind, dense, act_fmt=None):
    """One decoder block on one sequence x [s, hidden]."""
    eps = model["rms_norm_eps"]
    h = x + attention(rms_norm(x, lw["ln1"], eps), lw, model, kind, act_fmt)
    return h + feed_forward(rms_norm(h, lw["ln2"], eps), lw, model, dense,
                            act_fmt)


def layer_runs(model: dict):
    """[(first, end, kind, dense?)]: consecutive layers of one kind and
    one number of heads."""
    runs = []
    for i in range(model["num_hidden_layers"]):
        kind = (model["layer_types"][i], family.is_dense(model, i),
                family.heads(model, i))
        if runs and runs[-1][2:] == kind:
            runs[-1] = (runs[-1][0], i + 1) + kind
        else:
            runs.append((i, i + 1) + kind)
    return [run[:4] for run in runs]


def train_params(cfg: dict, seed: int, precision: str = "stated"):
    """Every leaf in float32, as the trainer's parameters start."""
    seeded = W.Leaves(family, cfg, seed)
    out = {}
    for name, shape in seeded.shapes.items():
        leaf = seeded.make(name).astype(F32)
        if precision == "lower" and len(shape) >= 2 and name != "embed":
            leaf = stored_fp8(leaf)
        out[name] = leaf
    return out


def sequence_hidden(params, ids, model, act_fmt=None):
    """The last norm's output [s, hidden] of one row ids [s]."""
    x = jnp.take(params["embed"], ids, axis=0)
    for lo, hi, kind, dense in layer_runs(model):
        stacked = {k: jnp.stack([params[f"layers.{i}.{k}"]
                                 for i in range(lo, hi)])
                   for k in family.layer_shapes(model, lo)}
        one = jax.checkpoint(functools.partial(
            layer, model=model, kind=kind, dense=dense, act_fmt=act_fmt))
        x, _ = jax.lax.scan(lambda x, lw: (one(x, lw), None), x, stacked)
    return rms_norm(x, params["norm"], model["rms_norm_eps"])


def sequence_logits_of(params, ids, model, act_fmt=None):
    """[s, vocab] logits of one row (the tests' comparison)."""
    return mm(sequence_hidden(params, ids, model, act_fmt), params["head"],
              act_fmt)


def row_loss(params, ids, model, act_fmt=None, block=2048):
    """Summed next-token cross entropy of one row ids [s], the head's
    logits a block of positions at a time."""
    h = sequence_hidden(params, ids, model, act_fmt)
    s = ids.shape[0]
    blk = min(block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    # position t is held against ids[t + 1]; the last has no target
    targets = jnp.roll(ids, -1)
    counts = (jnp.arange(s) < s - 1).astype(F32)

    @jax.checkpoint
    def ce(args):
        hh, tgt, w = args
        logits = mm(hh, params["head"], act_fmt)
        nll = jax.nn.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0]
        return jnp.sum(nll * w)

    cut = lambda a: a.reshape((s // blk, blk) + a.shape[1:])
    return jnp.sum(jax.lax.map(ce, (cut(h), cut(targets), cut(counts))))


@functools.lru_cache(maxsize=None)
def _row_value_and_grad(model_json: str, act_fmt):
    """One row's loss over ``n_ce`` and its gradients, jitted once a
    configuration and precision: every step of a check runs the one
    compiled program."""
    model = json.loads(model_json)
    return jax.jit(jax.value_and_grad(
        lambda leaves, ids, n_ce: row_loss(leaves, ids, model, act_fmt)
        / n_ce))


def loss_and_grads(params, batch, cfg: dict, precision: str = "stated",
                   rows=None):
    """The program's ``loss``: the mean shifted next-token loss over the
    batch; and its gradients for every leaf. One row at a time. ``rows``
    limits the mean to those rows (a planted fault)."""
    act_fmt = "fp8" if precision == "lower" else None
    batch = np.asarray(batch, np.int32)
    rows = list(range(batch.shape[0])) if rows is None else list(rows)
    n_ce = jnp.float32(len(rows) * (batch.shape[1] - 1))

    f = _row_value_and_grad(json.dumps(cfg["model"], sort_keys=True),
                            act_fmt)
    loss, grads = 0.0, None
    with jax.default_matmul_precision(HI):
        for r in rows:
            l_r, g_r = f(params, jnp.asarray(batch[r]), n_ce)
            loss += float(l_r)
            # the sum waits on the host (float32 there as here): beside a
            # row's activations the device then holds no second gradient
            # tree
            g_r = jax.device_get(g_r)
            grads = g_r if grads is None else \
                {k: grads[k] + g_r[k] for k in g_r}
    return loss, grads
