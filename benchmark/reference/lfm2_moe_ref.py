"""Plain float32 reference of the LFM2-MoE language model's equations as
``benchmark/configs/lfm2_*.json`` states them, in straightforward
``jax.numpy``. Per layer ``h += Op(RMSNorm(h)); h += FFN(RMSNorm(h))``:
the gated short convolution as three shifted products (``[B, C, X] =
x W_in``, ``c_t = sum_j w_j (B * X)_{t-2+j}`` with zeros before the first
token, ``(C * c) W_out``) or causal grouped-query attention with a norm
over each head of q and k and rotary embedding (half-split); the SwiGLU
MLP or sigmoid routing over all the experts in float32 (the 4 largest of
``sigmoid + bias`` chosen, weights ``sigmoid`` there over their sum +
1e-6, times the scaling factor) and, of the SwiGLU experts, only the
share this chip holds, each on every token under a mask. A last RMSNorm
and the tied head ``h E^T``.

Departures from the published description, each also in the
configuration file: the embedding is tied (the family's convention; the
config keeps no such key); what the absent experts would add is left
out, as in the program; the selection bias is zero unless ``params``
brings ``layers.{i}.eb`` (the tests do; the cell does not); no auxiliary
loss.

Imports nothing of the program: the leaves come again from the seed by
the family's list, every matmul runs at ``highest``, there is no kernel.
One sequence at a time, attention's queries in blocks under
``jax.checkpoint`` so that a block's [heads, block, seq] probabilities
are all that is held; consecutive layers of one kind are stacked and
scanned, so that each kind is compiled once.

``precision="lower"`` is the control: matrices stored in fp8-e4m3 (per
output channel, the embedding's gather apart) and every matmul input
rounded to fp8, the nearest precision below the stated bfloat16.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from ..families import lm_lfm2_moe as family
from .keye_vl2_ref import F32, HI, act_round, mm, rms_norm, rope, stored_fp8

BIAS = "eb"         # layers.{i}.eb: a selection bias the caller brings


def short_conv(x, lw, act_fmt=None):
    """The gated short convolution on one sequence x [s, hidden]."""
    b_, c_, x_ = jnp.split(mm(x, lw["ci"], act_fmt), 3, axis=-1)
    z = b_ * x_
    taps, s = lw["cw"].shape[0], z.shape[0]
    zp = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    conv = sum(lw["cw"][j] * zp[j:j + s] for j in range(taps))
    return mm(c_ * conv, lw["co"], act_fmt)


def causal_attention(q, k, v, q_block=512):
    """One sequence: q [s, nh, d]; k, v [s, kvh, d] -> [s, nh * d]."""
    s, nh, d = q.shape
    kvh = k.shape[1]
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    nb = s // blk

    @jax.checkpoint
    def block(args):
        qb, start = args
        rows = start + jnp.arange(blk)
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(causal[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v,
                          precision=HI).reshape(blk, nh * d)

    out = jax.lax.map(block, (q.reshape(nb, blk, kvh, nh // kvh, d),
                              jnp.arange(nb) * blk))
    return out.reshape(s, nh * d)


def attention(x, lw, model, act_fmt=None):
    nh, kvh = model["num_attention_heads"], model["num_key_value_heads"]
    d, eps, theta = family.head_dim(model), model["norm_eps"], \
        float(model["rope_theta"])
    s = x.shape[0]
    q = rope(rms_norm(mm(x, lw["wq"], act_fmt).reshape(s, nh, d),
                      lw["qn"], eps), theta)
    k = rope(rms_norm(mm(x, lw["wk"], act_fmt).reshape(s, kvh, d),
                      lw["kn"], eps), theta)
    v = mm(x, lw["wv"], act_fmt).reshape(s, kvh, d)
    return mm(causal_attention(q, k, v), lw["wo"], act_fmt)


def dense_mlp(x, lw, act_fmt=None):
    return mm(jax.nn.silu(mm(x, lw["w1"], act_fmt))
              * mm(x, lw["w3"], act_fmt), lw["w2"], act_fmt)


def route(x, lw, model, act_fmt=None):
    """(top_i [s, k], gates [s, k]) by the sigmoid rule."""
    s = jax.nn.sigmoid(mm(x, lw["wr"], act_fmt))
    _, top_i = jax.lax.top_k(s + lw[BIAS], model["num_experts_per_tok"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if model["norm_topk_prob"]:
        top_s = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-6)
    return top_i, top_s * float(model["routed_scaling_factor"])


def held_experts(x, lw, model, act_fmt=None):
    """The held experts' part of the expert layer's output for x
    [s, hidden]: each held expert on every token, weighted by the token's
    gate for it (nought where it was not chosen)."""
    first = model["expert_share"][0] * model["num_experts"]
    top_i, gates = route(x, lw, model, act_fmt)
    # rounded once, not once an expert: the scan would keep every copy
    # for its backward pass
    xr = act_round(x, act_fmt)

    def one(y, ew):
        wg, wu, wd, e = ew
        gate = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), -1)
        h = jax.nn.silu(mm(xr, wg)) * mm(xr, wu)
        return y + gate[:, None] * mm(h, wd, act_fmt), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lw["eg"], lw["eu"], lw["ed"], jnp.arange(model["num_experts"])))
    return y


def layer(x, lw, model, attn: bool, dense: bool, act_fmt=None):
    """One decoder block on one sequence x [s, hidden]."""
    eps = model["norm_eps"]
    hn = rms_norm(x, lw["ln1"], eps)
    x = x + (attention(hn, lw, model, act_fmt) if attn
             else short_conv(hn, lw, act_fmt))
    hn = rms_norm(x, lw["ln2"], eps)
    return x + (dense_mlp(hn, lw, act_fmt) if dense
                else held_experts(hn, lw, model, act_fmt))


def layer_runs(model: dict):
    """[(first, end, attention?, dense?)]: consecutive layers of one
    kind."""
    runs = []
    for i in range(model["num_hidden_layers"]):
        kind = (family.is_attention(model, i), family.is_dense(model, i))
        if runs and runs[-1][2:] == kind:
            runs[-1] = (runs[-1][0], i + 1) + kind
        else:
            runs.append((i, i + 1) + kind)
    return runs


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
    no_bias = jnp.zeros((family.router_width(model),), F32)
    for lo, hi, attn, dense in layer_runs(model):
        stacked = {k: jnp.stack([params[f"layers.{i}.{k}"]
                                 for i in range(lo, hi)])
                   for k in family.layer_shapes(model, lo)}
        if not dense:
            stacked[BIAS] = jnp.stack([
                params.get(f"layers.{i}.{BIAS}", no_bias)
                for i in range(lo, hi)])
        one = jax.checkpoint(functools.partial(
            layer, model=model, attn=attn, dense=dense, act_fmt=act_fmt))
        x, _ = jax.lax.scan(lambda x, lw: (one(x, lw), None), x, stacked)
    return rms_norm(x, params["norm"], model["norm_eps"])


def tied_head(params, act_fmt=None):
    """[hidden, vocab]: the embedding, transposed."""
    head = params["embed"].T
    return head if act_fmt is None else stored_fp8(head)


def sequence_logits_of(params, ids, model, act_fmt=None):
    """[s, vocab] logits of one row (the tests' comparison)."""
    return mm(sequence_hidden(params, ids, model, act_fmt),
              tied_head(params, act_fmt), act_fmt)


def row_loss(params, ids, model, act_fmt=None):
    """Summed next-token cross entropy of one row ids [s]."""
    h = sequence_hidden(params, ids, model, act_fmt)

    def ce(hh, tgt):
        logits = mm(hh, tied_head(params, act_fmt), act_fmt)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0])
    return jax.checkpoint(ce)(h[:-1], ids[1:])


def loss_and_grads(params, batch, cfg: dict, precision: str = "stated",
                   rows=None):
    """The program's ``loss``: the mean shifted next-token loss over the
    batch; and its gradients for every leaf. One row at a time. ``rows``
    limits the mean to those rows (a planted fault)."""
    model = cfg["model"]
    act_fmt = "fp8" if precision == "lower" else None
    batch = np.asarray(batch, np.int32)
    rows = list(range(batch.shape[0])) if rows is None else list(rows)
    n_ce = len(rows) * (batch.shape[1] - 1)
    bias = {k: v for k, v in params.items() if k.endswith("." + BIAS)}
    leaves = {k: v for k, v in params.items() if k not in bias}

    f = jax.jit(jax.value_and_grad(
        lambda leaves, ids: row_loss({**leaves, **bias}, ids, model,
                                     act_fmt) / n_ce))
    loss, grads = 0.0, None
    with jax.default_matmul_precision(HI):
        for r in rows:
            l_r, g_r = f(leaves, jnp.asarray(batch[r]))
            loss += float(l_r)
            # the sum waits on the host (float32 there as here): beside a
            # row's activations the device then holds no second gradient
            # tree
            g_r = jax.device_get(g_r)
            grads = g_r if grads is None else \
                {k: grads[k] + g_r[k] for k in g_r}
    return loss, grads
