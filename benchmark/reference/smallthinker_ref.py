"""Plain float32 reference of the SmallThinker language model's equations
as ``benchmark/configs/smallthinker_*.json`` states them, in
straightforward ``jax.numpy``. Layer ``i`` on its input ``h``: the router
reads ``h`` itself (softmax over all the experts in float32, the 6
largest, divided by their sum); ``h += Attention(RMSNorm(h))``,
grouped-query attention under an explicit mask, causal and, where
``sliding_window_layout[i]`` is 1, banded (query t sees the keys s with
``t - window < s <= t``), q and k under the rotary embedding (half-split)
where ``rope_layout[i]`` is 1 and bare where it is 0; ``h +=
Experts(RMSNorm(h))``, ``w_down(relu(w_gate u) * w_up u)`` weighted by
that routing, of which only the share this chip holds is computed, each
held expert on every token under a mask. A last RMSNorm and the untied
head.

Departures from the published description, each also in the
configuration file: what the absent experts would add is left out, as in
the program; no "secondary" experts (no key of the config); no auxiliary
loss.

Imports nothing of the program: the leaves come again from the seed by
the family's list, every matmul runs at ``highest``, there is no kernel.
One sequence at a time; attention's queries in blocks under
``jax.checkpoint`` so that a block's [heads, block, seq] probabilities
are all that is held, the head and the cross entropy in blocks of
positions likewise; the layers are stacked and scanned with each layer's
window and rotary flag as data (a layer that sees every key has a window
of ``NO_WINDOW`` keys), so that one layer is compiled for all.

``precision="lower"`` is the control: matrices stored in fp8-e4m3 (per
output channel, the embedding's gather apart) and every matmul input
rounded to fp8, the nearest precision below the stated bfloat16.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from ..families import lm_smallthinker as family
from .keye_vl2_ref import F32, HI, act_round, mm, rms_norm, rope, stored_fp8


NO_WINDOW = 1 << 30     # keys: more than any sequence has


def banded_attention(q, k, v, window, q_block=256):
    """One sequence: q [s, nh, d]; k, v [s, kvh, d] -> [s, nh * d]; a
    query sees the keys at or before it and no key ``window`` (a whole
    number, maybe traced) or more positions back."""
    s, nh, d = q.shape
    kvh = k.shape[1]
    blk = min(q_block, s)
    if s % blk:
        raise ValueError(f"sequence {s} is not a multiple of {blk}")
    nb = s // blk

    @jax.checkpoint
    def block(args):
        qb, start = args
        rows = (start + jnp.arange(blk))[:, None]
        cols = jnp.arange(s)[None, :]
        seen = (cols <= rows) & (cols > rows - window)
        sc = jnp.einsum("qkgd,skd->kgqs", qb, k, precision=HI) / np.sqrt(d)
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("kgqs,skd->qkgd", p, v,
                          precision=HI).reshape(blk, nh * d)

    out = jax.lax.map(block, (q.reshape(nb, blk, kvh, nh // kvh, d),
                              jnp.arange(nb) * blk))
    return out.reshape(s, nh * d)


def attention(x, lw, model, window, rotary, act_fmt=None):
    """``rotary`` (a boolean, maybe traced): whether q and k take the
    rotary embedding or stay bare."""
    nh, kvh, d = model["num_attention_heads"], \
        model["num_key_value_heads"], model["head_dim"]
    s, theta = x.shape[0], float(model["rope_theta"])
    q = mm(x, lw["wq"], act_fmt).reshape(s, nh, d)
    k = mm(x, lw["wk"], act_fmt).reshape(s, kvh, d)
    v = mm(x, lw["wv"], act_fmt).reshape(s, kvh, d)
    q = jnp.where(rotary, rope(q, theta), q)
    k = jnp.where(rotary, rope(k, theta), k)
    return mm(banded_attention(q, k, v, window), lw["wo"], act_fmt)


def route(x, lw, model, act_fmt=None):
    """(top_i [s, k], gates [s, k]) of the tokens x [s, hidden]: softmax
    over every expert, the largest chosen, renormalised."""
    probs = jax.nn.softmax(mm(x, lw["wr"], act_fmt), axis=-1)
    top_p, top_i = jax.lax.top_k(probs,
                                 model["moe_num_active_primary_experts"])
    if model["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_i, top_p


def held_experts(u, routed_on, lw, model, act_fmt=None):
    """The held experts' part of the layer's output for u [s, hidden],
    routed by ``routed_on`` [s, hidden]: each held expert on every token,
    weighted by the token's gate for it (nought where it was not
    chosen)."""
    first = model["expert_share"][0] * model["moe_num_primary_experts"]
    top_i, gates = route(routed_on, lw, model, act_fmt)
    # rounded once, not once an expert: the scan would keep every copy
    # for its backward pass
    ur = act_round(u, act_fmt)

    # made again in the backward pass, an expert at a time: kept, the 16
    # experts' [s, width] and [s, hidden] products would be 6 GB at 16,384
    # tokens
    @jax.checkpoint
    def one(ew):
        wg, wu, wd, e = ew
        gate = jnp.sum(jnp.where(top_i == first + e, gates, 0.0), -1)
        h = jax.nn.relu(mm(ur, wg)) * mm(ur, wu)
        return gate[:, None] * mm(h, wd, act_fmt)

    y, _ = jax.lax.scan(
        lambda y, ew: (y + one(ew), None), jnp.zeros_like(u),
        (lw["eg"], lw["eu"], lw["ed"],
         jnp.arange(model["moe_num_primary_experts"])))
    return y


def layer(x, lw, model, window, rotary, act_fmt=None):
    """One decoder block on one sequence x [s, hidden]."""
    eps = model["rms_norm_eps"]
    h = x + attention(rms_norm(x, lw["ln1"], eps), lw, model, window,
                      rotary, act_fmt)
    return h + held_experts(rms_norm(h, lw["ln2"], eps), x, lw, model,
                            act_fmt)


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
    layers = range(model["num_hidden_layers"])
    stacked = {k: jnp.stack([params[f"layers.{i}.{k}"] for i in layers])
               for k in family.layer_shapes(model)}
    windows = jnp.asarray([family.window_of(model, i) or NO_WINDOW
                           for i in layers], jnp.int32)
    rotary = jnp.asarray(model["rope_layout"], bool)
    one = jax.checkpoint(functools.partial(layer, model=model,
                                           act_fmt=act_fmt))
    x, _ = jax.lax.scan(
        lambda x, per: (one(x, per[0], window=per[1], rotary=per[2]), None),
        x, (stacked, windows, rotary))
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

    f = jax.jit(jax.value_and_grad(
        lambda leaves, ids: row_loss(leaves, ids, model, act_fmt) / n_ce))
    loss, grads = 0.0, None
    with jax.default_matmul_precision(HI):
        for r in rows:
            l_r, g_r = f(params, jnp.asarray(batch[r]))
            loss += float(l_r)
            # the sum waits on the host (float32 there as here): beside a
            # row's activations the device then holds no second gradient
            # tree
            g_r = jax.device_get(g_r)
            grads = g_r if grads is None else \
                {k: grads[k] + g_r[k] for k in g_r}
    return loss, grads
