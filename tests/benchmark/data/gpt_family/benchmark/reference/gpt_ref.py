"""Plain float32 reference of the GPT-2 equations (learned positions,
LayerNorm, fused QKV, causal attention, exact GELU, tied head) in
straightforward ``jax.numpy``; imports nothing of the program. The
control (``precision="lower"``) keeps matrices and matmul inputs in
bfloat16, the step below the float32 this fixture's configuration states.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights as W
from ..families import gpt as family

F32 = jnp.float32
HI = "highest"


def _round(x, lower: bool):
    return x.astype(jnp.bfloat16).astype(F32) if lower else x


def train_params(cfg: dict, seed: int, precision: str = "stated"):
    seeded = W.Leaves(family, cfg, seed)
    return {name: _round(seeded.make(name).astype(F32),
                         precision == "lower" and len(shape) == 2)
            for name, shape in seeded.shapes.items()}


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def row_loss_sum(params, ids, model, lower=False):
    """Summed next-token cross entropy of one row ids [s]."""
    def mm(x, w):
        return jnp.matmul(_round(x, lower), w, precision=HI)

    s, nh = ids.shape[0], model["num_attention_heads"]
    eps = model["layer_norm_epsilon"]
    x = params["wte"][ids] + params["wpe"][:s]
    for i in range(model["num_hidden_layers"]):
        p = {k: params[f"h.{i}.{k}"] for k in (
            "ln1.g", "ln1.b", "qkv.w", "qkv.b", "proj.w", "proj.b",
            "ln2.g", "ln2.b", "fc.w", "fc.b", "out.w", "out.b")}
        qkv = mm(layer_norm(x, p["ln1.g"], p["ln1.b"], eps), p["qkv.w"]) \
            + p["qkv.b"]
        q, k, v = jnp.moveaxis(qkv.reshape(s, 3, nh, -1), 1, 0)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) \
            / np.sqrt(q.shape[-1])
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        att = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v,
                         precision=HI).reshape(s, -1)
        x = x + mm(att, p["proj.w"]) + p["proj.b"]
        hid = jax.nn.gelu(mm(layer_norm(x, p["ln2.g"], p["ln2.b"], eps),
                             p["fc.w"]) + p["fc.b"], approximate=False)
        x = x + mm(hid, p["out.w"]) + p["out.b"]
    h = layer_norm(x, params["lnf.g"], params["lnf.b"], eps)
    logits = mm(h[:-1], params["wte"].T)
    return jnp.sum(jax.nn.logsumexp(logits, -1)
                   - jnp.take_along_axis(logits, ids[1:, None], 1)[:, 0])


def loss_and_grads(params, batch, cfg: dict, precision: str = "stated",
                   rows=None):
    batch = np.asarray(batch, np.int32)
    rows = list(range(batch.shape[0])) if rows is None else list(rows)
    denom = len(rows) * (batch.shape[1] - 1)
    f = jax.jit(jax.value_and_grad(functools.partial(
        row_loss_sum, model=cfg["model"], lower=precision == "lower")))
    loss, grads = 0.0, None
    for r in rows:
        l_r, g_r = f(params, jnp.asarray(batch[r]))
        loss += float(l_r)
        grads = g_r if grads is None else jax.tree.map(jnp.add, grads, g_r)
    return loss / denom, jax.tree.map(lambda a: a / denom, grads)
