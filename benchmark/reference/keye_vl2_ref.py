"""Plain float32 reference of the Keye-VL-2.0 language model's equations
as ``benchmark/configs/keye_vl2_*.json`` states them, in straightforward
``jax.numpy``: RMSNorm; q, k, v with a norm over each head of q and k and
rotary embedding (half-split); the indexer (16 heads of 64 over one key
head, relu scores weighted per head); each query's exact top-k causal
keys, ties to the lower index; softmax attention over the kept keys; the
indexer's KL loss against the heads' summed probabilities, with the
target and the indexer's input under ``stop_gradient``; softmax routing
over all the experts in float32, the top 8 renormalised, and of the
SwiGLU experts only the share this chip holds (what the absent experts
would add is left out, as in the program).

Imports nothing of the program: the leaves come again from the seed by
the family's list, every matmul runs at ``highest``, there is no kernel.
One sequence at a time, its queries in blocks under ``jax.checkpoint``
so that a block's [heads, block, seq] probabilities are all that is held;
the selection is a sort per row, not the program's bitwise search.

``precision="lower"`` is the control: matrices stored in fp8-e4m3 (per
output channel, the embedding apart) and every matmul input rounded to
fp8, the nearest precision below the stated bfloat16.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from ..families import lm_keye_vl2 as family

F32 = jnp.float32
HI = "highest"
LAYER_KEYS = tuple(family._TRAIN_NAMES) + tuple(family._MOE_NAMES)


def stored_fp8(w):
    """The float32 value of a matrix kept in fp8-e4m3 with one scale per
    output channel (the last axis)."""
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def act_round(x, fmt):
    if fmt is None:
        return x
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def mm(x, w, act_fmt=None):
    return jnp.matmul(act_round(x, act_fmt), w, precision=HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def rope(x, theta):
    """x [s, heads, d], positions 0..s-1, half-split rotation over all d."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def kept_keys(scores, causal, topk):
    """Boolean [rows, seq]: the min(causal keys, topk) best-scored causal
    keys of each row, ties to the lower index. By sorting: the topk-th
    largest value is the bar, everything above it is kept, and of the
    keys AT the bar the first ones, as many as are still missing."""
    seq = scores.shape[-1]
    x = jnp.where(causal, scores, -jnp.inf)
    if topk >= seq:
        return causal
    bar = jnp.sort(x, axis=-1)[:, seq - topk][:, None]
    above, at = x > bar, (x == bar) & causal
    missing = topk - jnp.sum(above, -1, keepdims=True)
    return causal & (above | (at & (jnp.cumsum(at, -1) <= missing)))


def sparse_attention(q, k, v, qi, ki, w, topk, q_block=512):
    """One sequence: q [s, nh, d]; k, v [s, kvh, d]; qi [s, hi, di];
    ki [s, di]; w [s, hi] (scales folded in). Returns (out [s, nh * d],
    the sum over the queries of the indexer's KL term)."""
    s, nh, d = q.shape
    kvh = k.shape[1]
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    nb = s // blk
    qg = q.reshape(nb, blk, kvh, nh // kvh, d)

    @jax.checkpoint
    def block(args):
        qb, qib, wb, start = args
        rows = start + jnp.arange(blk)
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        dots = jnp.einsum("qjd,sd->qjs", qib, ki, precision=HI)
        scores = jnp.einsum("qjs,qj->qs", jnp.maximum(dots, 0.0), wb,
                            precision=HI)
        keep = kept_keys(jax.lax.stop_gradient(scores), causal, topk)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(keep[None, None], sc, -jnp.inf), -1)
        out = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
        target = jax.lax.stop_gradient(jnp.sum(p, axis=(0, 1)))
        target = target / jnp.sum(target, -1, keepdims=True)
        logq = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        live = keep & (target > 0)
        kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                       - jnp.where(live, logq, 0.0)), 0.0)
        return out.reshape(blk, nh * d), jnp.sum(kl)

    out, kl = jax.lax.map(block, (
        qg, qi.reshape(nb, blk, *qi.shape[1:]), w.reshape(nb, blk, -1),
        jnp.arange(nb) * blk))
    return out.reshape(s, nh * d), jnp.sum(kl)


def held_experts(x, lw, model, act_fmt=None):
    """The held experts' part of the expert layer's output for x
    [s, hidden]: routing over the router's whole width, then each held
    expert on every token, weighted by the token's gate for it (nought
    where it was not chosen)."""
    first = model["expert_share"][0] * model["num_experts"]
    probs = jax.nn.softmax(mm(x, lw["wr"], act_fmt), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, model["num_experts_per_tok"])
    gates = top_p / jnp.sum(top_p, -1, keepdims=True) \
        if model["norm_topk_prob"] else top_p

    # rounded once, not once an expert: the scan would keep every copy
    # for its backward pass, and the control would not fit the chip
    xr = act_round(x, act_fmt)

    def one(y, ew):
        wg, wu, wd, e = ew
        gate = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), -1)
        h = jax.nn.silu(mm(xr, wg)) * mm(xr, wu)
        return y + gate[:, None] * mm(h, wd, act_fmt), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lw["eg"], lw["eu"], lw["ed"], jnp.arange(model["num_experts"])))
    return y


def layer(x, lw, model, act_fmt=None):
    """One decoder block on one sequence x [s, hidden] -> (x, the sum
    over the queries of the indexer's KL term)."""
    nh, kvh, d = model["num_attention_heads"], \
        model["num_key_value_heads"], model["head_dim"]
    sa = model["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    eps, theta, s = model["rms_norm_eps"], float(model["rope_theta"]), \
        x.shape[0]
    hn = rms_norm(x, lw["ln1"], eps)
    q = rope(rms_norm(mm(hn, lw["wq"], act_fmt).reshape(s, nh, d),
                      lw["qn"], eps), theta)
    k = rope(rms_norm(mm(hn, lw["wk"], act_fmt).reshape(s, kvh, d),
                      lw["kn"], eps), theta)
    v = mm(hn, lw["wv"], act_fmt).reshape(s, kvh, d)
    hs = jax.lax.stop_gradient(hn)
    qi = rope(mm(hs, lw["iwq"], act_fmt).reshape(s, hi, di), theta)
    ki = rope(rms_norm(mm(hs, lw["iwk"], act_fmt), lw["ikn"],
                       eps)[:, None], theta)[:, 0]
    w = mm(hs, lw["iww"], act_fmt) * (di ** -0.5 * hi ** -0.5)
    o, kl = sparse_attention(q, k, v, qi, ki, w, sa["topk"])
    x = x + mm(o, lw["wo"], act_fmt)
    hn = rms_norm(x, lw["ln2"], eps)
    return x + held_experts(hn, lw, model, act_fmt), kl


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


def row_losses(params, ids, model, act_fmt=None):
    """(summed next-token cross entropy, the layers' summed KL terms) of
    one row ids [s]."""
    x = jnp.take(params["embed"], ids, axis=0)
    # the layers are alike: stacked and scanned, so that one layer is
    # compiled, each under jax.checkpoint
    stacked = {k: jnp.stack([params[f"layers.{i}.{k}"]
                             for i in range(model["num_hidden_layers"])])
               for k in LAYER_KEYS}
    one = jax.checkpoint(functools.partial(layer, model=model,
                                           act_fmt=act_fmt))

    def body(carry, lw):
        x, kl = carry
        x, kl_i = one(x, lw)
        return (x, kl + kl_i), None

    (x, kl), _ = jax.lax.scan(body, (x, jnp.zeros((), F32)), stacked)
    h = rms_norm(x, params["norm"], model["rms_norm_eps"])

    def ce(hh, tgt):
        logits = mm(hh, params["head"], act_fmt)
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1)
                       - jnp.take_along_axis(logits, tgt[:, None], 1)[:, 0])
    return jax.checkpoint(ce)(h[:-1], ids[1:]), kl


def loss_and_grads(params, batch, cfg: dict, precision: str = "stated",
                   rows=None):
    """The program's ``loss``: the mean shifted next-token loss over the
    batch plus, with weight 1, every layer's indexer loss (the mean of
    its KL term over all the batch's queries); and its gradients. One row
    at a time. ``rows`` limits both means to those rows (a planted
    fault)."""
    model = cfg["model"]
    act_fmt = "fp8" if precision == "lower" else None
    batch = np.asarray(batch, np.int32)
    rows = list(range(batch.shape[0])) if rows is None else list(rows)
    n_ce, n_kl = len(rows) * (batch.shape[1] - 1), len(rows) * batch.shape[1]

    def row_loss(params, ids):
        ce, kl = row_losses(params, ids, model, act_fmt)
        return ce / n_ce + kl / n_kl

    f = jax.jit(jax.value_and_grad(row_loss))
    loss, grads = 0.0, None
    with jax.default_matmul_precision(HI):
        for r in rows:
            l_r, g_r = f(params, jnp.asarray(batch[r]))
            loss += float(l_r)
            # the sum waits on the host (float32 there as here): beside a
            # row's activations the device then holds no second gradient
            # tree, which the control's larger program has no room for
            g_r = jax.device_get(g_r)
            grads = g_r if grads is None else \
                {k: grads[k] + g_r[k] for k in g_r}
    return loss, grads
