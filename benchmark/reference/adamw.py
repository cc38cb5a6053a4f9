"""Decoupled-weight-decay Adam and the norms per leaf, in float32
``jax.numpy``: what every family's reference is stepped and measured
with (``benchmark.check.reference_train_readings``)."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


@functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(0, 1, 2, 3))
def _adamw_leaf(p, g, m_, v_, hp, t):
    lr, b1, b2, eps, wd = hp
    m_ = b1 * m_ + (1 - b1) * g
    v_ = b2 * v_ + (1 - b2) * g * g
    upd = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
    return p * (1 - lr * wd) - lr * upd, m_, v_


def adamw_step(params, grads, state, opt: dict, t: int):
    """Decoupled-weight-decay Adam on every leaf. ``state`` is {"m": {},
    "v": {}} of host arrays (empty before the first step): the moments
    wait on the host between steps, so that the device holds the
    parameters, one gradient tree and a row's activations, no more."""
    hp = (opt["learning_rate"], opt["beta1"], opt["beta2"], opt["epsilon"],
          opt["weight_decay"])
    for name in list(params):
        g = grads.pop(name)
        m_ = jnp.asarray(state["m"][name]) if name in state["m"] \
            else jnp.zeros_like(g)
        v_ = jnp.asarray(state["v"][name]) if name in state["v"] \
            else jnp.zeros_like(g)
        params[name], m_, v_ = _adamw_leaf(
            params[name], g, m_, v_, hp, jnp.asarray(float(t), F32))
        state["m"][name], state["v"][name] = np.asarray(m_), np.asarray(v_)
    return params, state


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))))
            for k, v in tree.items()}
