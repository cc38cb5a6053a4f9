"""Loss functionals (parity:
/root/reference/python/paddle/nn/functional/loss.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...framework.core import Tensor, apply, apply_nodiff
from ...utils import telemetry

__all__ = [
    "cross_entropy", "softmax_with_cross_entropy", "mse_loss", "l1_loss",
    "nll_loss", "binary_cross_entropy", "binary_cross_entropy_with_logits",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "cosine_similarity",
    "cosine_embedding_loss", "label_smooth", "square_error_cost",
    "log_loss", "hinge_embedding_loss", "triplet_margin_loss",
    "sigmoid_focal_loss", "ctc_loss", "poisson_nll_loss",
    "chunked_softmax_cross_entropy",
]


def _reduce(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def _ce_hard_fwd(logits, idx, ignore_index, reduction):
    """(loss, d): the cross entropy of integer labels over the last axis
    and its gradient w.r.t. the logits IN THE LOGITS' DTYPE, already
    scaled by the reduction's denominator, both made in the forward
    pass. The float32 cast lives in registers: nothing float32 of the
    logits' shape leaves the fusions that make the row sums and ``d``."""
    x = logits.astype(jnp.float32)
    valid = idx != ignore_index
    onehot = jax.lax.broadcasted_iota(idx.dtype, x.shape, x.ndim - 1) \
        == jnp.where(valid, idx, 0)[..., None]
    shifted = x - jax.lax.stop_gradient(jnp.max(x, axis=-1, keepdims=True))
    e = jnp.exp(shifted)
    s = jnp.sum(e, axis=-1)
    # log_softmax's own arithmetic: -(shifted[idx] - log(sum(exp(shifted))))
    picked = jnp.sum(jnp.where(onehot, shifted, 0.0), axis=-1)
    loss = jnp.where(valid, jnp.log(s) - picked, 0.0)
    rows = valid.astype(jnp.float32)
    if reduction == "mean":
        rows = rows / jnp.maximum(jnp.sum(rows), 1.0)
    if reduction != "none":
        loss = jnp.sum(loss * rows)
    d = e * (rows / s)[..., None]
    d = jnp.where(onehot, d - rows[..., None], d)
    return loss, d.astype(logits.dtype)


@functools.partial(jax.custom_jvp, nondiff_argnums=(2, 3))
def _ce_hard(logits, idx, ignore_index, reduction):
    return _ce_hard_fwd(logits, idx, ignore_index, reduction)[0]


@_ce_hard.defjvp
def _ce_hard_jvp(ignore_index, reduction, primals, tangents):
    """tangent = <d, t> with float32 accumulation. ``d`` is the only
    thing the linear part reads, so it is the one residual of a vjp, and
    the transpose is ``(g * d)`` rounded once to the logits' dtype: the
    rounding autodiff's transpose of the float32 cast made."""
    logits, idx = primals
    loss, d = _ce_hard_fwd(logits, idx, ignore_index, reduction)
    t_loss = jnp.einsum(
        "...v,...v->..." if reduction == "none" else "...v,...v->",
        d, tangents[0], preferred_element_type=jnp.float32)
    return loss, t_loss


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """Integer labels over the last axis with ``use_softmax`` and neither
    class weights nor smoothing (what every language model here calls)
    take ``_ce_hard``: its vjp keeps ONE residual, the gradient w.r.t.
    the logits in the logits' dtype, made in the forward pass. Every
    other case is differentiated by jax through the body below, whose
    residual is the float32 log-probabilities. Which of the two a call
    took is counted in the default registry
    (``loss.cross_entropy.grad_in_forward`` / ``.autodiff``)."""
    def f(logits, lbl, *w):
        n_classes = logits.shape[axis]
        is_soft = soft_label or (lbl.ndim == logits.ndim
                                 and lbl.shape == logits.shape)
        hard_rule = (not is_soft and not w and use_softmax
                     and label_smoothing == 0
                     and axis % logits.ndim == logits.ndim - 1
                     and jnp.issubdtype(lbl.dtype, jnp.integer)
                     and reduction in ("mean", "sum", "none"))
        telemetry.default_tracer().metrics.inc(
            "loss.cross_entropy."
            + ("grad_in_forward" if hard_rule else "autodiff"))
        if hard_rule:
            idx = jnp.squeeze(lbl, -1) if lbl.ndim == logits.ndim else lbl
            return _ce_hard(logits, idx, ignore_index, reduction)
        if use_softmax:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
        else:
            logp = jnp.log(jnp.maximum(logits.astype(jnp.float32), 1e-30))
        if is_soft:
            soft = lbl.astype(logp.dtype)
            if label_smoothing > 0:
                soft = soft * (1 - label_smoothing) + label_smoothing / n_classes
            loss = -jnp.sum(soft * logp, axis=axis)
            valid = jnp.ones_like(loss, dtype=jnp.bool_)
        else:
            idx = lbl
            if idx.ndim == logp.ndim:  # trailing 1 dim
                idx = jnp.squeeze(idx, axis=axis)
            valid = idx != ignore_index
            safe_idx = jnp.where(valid, idx, 0)
            picked = jnp.take_along_axis(
                logp, jnp.expand_dims(safe_idx, axis), axis=axis)
            picked = jnp.squeeze(picked, axis=axis)
            if label_smoothing > 0:
                smooth_loss = -jnp.mean(logp, axis=axis)
                loss = -(1 - label_smoothing) * picked + label_smoothing * smooth_loss
            else:
                loss = -picked
            if w:
                cw = jnp.take(w[0].astype(logp.dtype), safe_idx)
                loss = loss * cw
            loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(valid.astype(logp.dtype)), 1.0)
            if w and not soft_label:
                cw = jnp.take(w[0].astype(logp.dtype), jnp.where(valid, lbl if lbl.ndim == loss.ndim else jnp.squeeze(lbl, axis=axis), 0))
                denom = jnp.maximum(jnp.sum(jnp.where(valid, cw, 0.0)), 1e-12)
            return jnp.sum(loss) / denom
        return _reduce(loss, reduction)

    args = [input, label] + ([weight] if weight is not None else [])
    return apply("cross_entropy", f, *args)


def softmax_with_cross_entropy(logits, label, soft_label=False, axis=-1,
                               ignore_index=-100, return_softmax=False,
                               numeric_stable_mode=True):
    loss = cross_entropy(logits, label, soft_label=soft_label, axis=axis,
                         ignore_index=ignore_index, reduction="none")
    from .activation import softmax as _softmax
    if return_softmax:
        return loss, _softmax(logits, axis=axis)
    return loss


def mse_loss(input, label, reduction="mean", name=None):
    return apply("mse_loss",
                 lambda a, b: _reduce(jnp.square(a - b), reduction), input, label)


def l1_loss(input, label, reduction="mean", name=None):
    return apply("l1_loss",
                 lambda a, b: _reduce(jnp.abs(a - b), reduction), input, label)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    def f(logp, lbl, *w):
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, 1), axis=1)
        loss = -jnp.squeeze(picked, 1)
        if w:
            cw = jnp.take(w[0], safe)
            loss = loss * cw
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            denom = jnp.sum(jnp.take(w[0], safe) * valid) if w else jnp.sum(valid)
            return jnp.sum(loss) / jnp.maximum(denom, 1e-12)
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply("nll_loss", f, *args)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def f(p, y, *w):
        p = jnp.clip(p, 1e-12, 1.0 - 1e-12)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply("bce", f, *args)


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    def f(z, y, *rest):
        logp = jax.nn.log_sigmoid(z)
        lognotp = jax.nn.log_sigmoid(-z)
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = rest[i]; i += 1
        if pos_weight is not None:
            pw = rest[i]; i += 1
        pos_term = y * logp * (pw if pw is not None else 1.0)
        loss = -(pos_term + (1 - y) * lognotp)
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)
    args = [logit, label]
    if weight is not None:
        args.append(weight)
    if pos_weight is not None:
        args.append(pos_weight)
    return apply("bce_logits", f, *args)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def f(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d, delta * (d - 0.5 * delta))
        return _reduce(loss, reduction)
    return apply("smooth_l1", f, input, label)


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def f(logq, p):
        if log_target:
            loss = jnp.exp(p) * (p - logq)
        else:
            loss = p * (jnp.log(jnp.maximum(p, 1e-30)) - logq)
        if reduction == "batchmean":
            return jnp.sum(loss) / logq.shape[0]
        return _reduce(loss, reduction)
    return apply("kl_div", f, input, label)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    def f(a, b, y):
        loss = jnp.maximum(0.0, -y * (a - b) + margin)
        return _reduce(loss, reduction)
    return apply("margin_ranking", f, input, other, label)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def f(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.sqrt(jnp.sum(a * a, axis=axis))
        nb = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(na * nb, eps)
    return apply("cosine_similarity", f, x1, x2)


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    def f(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(loss, reduction)
    return apply("cosine_embedding", f, input1, input2, label)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def f(y, *pd):
        k = y.shape[-1]
        if pd:
            return (1 - epsilon) * y + epsilon * pd[0]
        return (1 - epsilon) * y + epsilon / k
    args = [label] + ([prior_dist] if prior_dist is not None else [])
    return apply("label_smooth", f, *args)


def square_error_cost(input, label):
    return apply("square_error_cost", lambda a, b: jnp.square(a - b), input, label)


def log_loss(input, label, epsilon=1e-4, name=None):
    def f(p, y):
        return -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(1 - p + epsilon)
    return apply("log_loss", f, input, label)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    def f(a, y):
        loss = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce(loss, reduction)
    return apply("hinge_embedding", f, input, label)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def f(a, pos, neg):
        def dist(u, v):
            return jnp.power(jnp.sum(jnp.power(jnp.abs(u - v) + epsilon, p),
                                     axis=-1), 1.0 / p)
        d_pos = dist(a, pos)
        d_neg = dist(a, neg)
        if swap:
            d_neg = jnp.minimum(d_neg, dist(pos, neg))
        loss = jnp.maximum(0.0, d_pos - d_neg + margin)
        return _reduce(loss, reduction)
    return apply("triplet_margin", f, input, positive, negative)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def f(z, y, *n):
        p = jax.nn.sigmoid(z)
        ce = -(y * jax.nn.log_sigmoid(z) + (1 - y) * jax.nn.log_sigmoid(-z))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * jnp.power(1 - p_t, gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce(loss, reduction)
    args = [logit, label] + ([normalizer] if normalizer is not None else [])
    return apply("sigmoid_focal", f, *args)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """Connectionist temporal classification loss (parity:
    /root/reference/python/paddle/nn/functional/loss.py:1820, warpctc
    kernel). TPU-native: the CTC forward algorithm's alpha recursion over
    the blank-interleaved extended label sequence, as one lax.scan over
    time in log space — fully differentiable, so the gradient is the
    exact autodiff of the forward algorithm (warpctc computes the same
    thing by hand with a beta sweep).

    log_probs: [T, B, C] raw logits (softmax is applied internally, like
    warpctc); labels: [B, L] int; lengths: [B]. norm_by_times scales the
    GRADIENT by 1/T (the loss value is unchanged — warpctc semantics).
    reduction='mean' divides per-sample loss by label length then means.
    """
    def f(logits, lab, t_len, u_len):
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        t_max, b, _ = lp.shape
        l_max = lab.shape[1]
        s_max = 2 * l_max + 1
        lab = lab.astype(jnp.int32)
        t_len = t_len.astype(jnp.int32)
        u_len = u_len.astype(jnp.int32)
        # extended label sequence: blank a1 blank a2 ... blank
        ext = jnp.full((b, s_max), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab)
        neg_inf = -1e30

        def emit(t):
            # [B, S] log prob of emitting ext symbol at time t
            return jnp.take_along_axis(lp[t], ext, axis=1)

        alpha0 = jnp.full((b, s_max), neg_inf)
        alpha0 = alpha0.at[:, 0].set(emit(0)[:, 0])
        alpha0 = alpha0.at[:, 1].set(
            jnp.where(u_len > 0, emit(0)[:, 1], neg_inf))

        # the s-2 skip is legal only when ext[s] is a label differing
        # from ext[s-2] (can't skip the separating blank between equal
        # labels, nor skip into a blank)
        same_as_prev2 = jnp.concatenate(
            [jnp.ones((b, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1)

        def step(alpha, t):
            a_shift1 = jnp.concatenate(
                [jnp.full((b, 1), neg_inf), alpha[:, :-1]], axis=1)
            a_shift2 = jnp.concatenate(
                [jnp.full((b, 2), neg_inf), alpha[:, :-2]], axis=1)
            a_shift2 = jnp.where(same_as_prev2, neg_inf, a_shift2)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, a_shift1),
                                   a_shift2)
            new = merged + emit(t)
            # frozen past each sample's input length
            new = jnp.where((t < t_len)[:, None], new, alpha)
            return new, None

        alpha, _ = jax.lax.scan(step, alpha0, jnp.arange(1, t_max))
        # total prob: final blank (s=2U) or final label (s=2U-1)
        send = 2 * u_len
        last_blank = jnp.take_along_axis(alpha, send[:, None],
                                         axis=1)[:, 0]
        last_lab = jnp.take_along_axis(
            alpha, jnp.maximum(send - 1, 0)[:, None], axis=1)[:, 0]
        last_lab = jnp.where(u_len > 0, last_lab, neg_inf)
        nll = -jnp.logaddexp(last_blank, last_lab)
        if norm_by_times:
            # warpctc scales only the GRADIENT by 1/T; keep the value
            # and route autodiff through the scaled branch
            scaled = nll / jnp.maximum(t_len, 1).astype(nll.dtype)
            nll = scaled + jax.lax.stop_gradient(nll - scaled)
        return nll.astype(logits.dtype)

    loss = apply("ctc_loss", f, log_probs, labels, input_lengths,
                 label_lengths)
    if reduction == "mean":
        # reference (loss.py:1962): mean of per-sample loss normalized
        # by label length
        norm = apply("ctc_norm",
                     lambda l, ll: l / jnp.maximum(ll.astype(l.dtype),
                                                   1.0),
                     loss, label_lengths)
        return norm.mean()
    if reduction == "sum":
        return loss.sum()
    return loss


def chunked_softmax_cross_entropy(hidden, labels, weight,
                                  chunk_tokens: int,
                                  transpose_weight: bool = False,
                                  ignore_index: int = -100):
    """Head-matmul + shifted-CE computed in token chunks under
    jax.checkpoint — the [N, V] logits are never materialized; the
    backward rematerializes one chunk at a time. Serves every CausalLM
    in the zoo (the memory pressure is identical across them).

    hidden [B, S, D]; labels [B, S] (shift applied here, like the dense
    loss paths); weight [D, V] (or [V, D] with transpose_weight=True,
    the tied-embedding layout). ignore_index positions are masked from
    numerator AND denominator — exact parity with
    cross_entropy(ignore_index=...)."""
    def f(h, y, wv):
        b, s, d = h.shape
        hs = h[:, :-1].reshape(b * (s - 1), d)
        ys = y[:, 1:].reshape(-1)
        n = hs.shape[0]
        nc = -(-n // chunk_tokens)
        pad = nc * chunk_tokens - n
        hs = jnp.pad(hs, ((0, pad), (0, 0)))
        ys = jnp.pad(ys, (0, pad), constant_values=ignore_index)
        valid = (ys != ignore_index)
        mask = valid.astype(jnp.float32)
        ys_safe = jnp.where(valid, ys, 0)
        hs = hs.reshape(nc, chunk_tokens, d)
        ys_safe = ys_safe.reshape(nc, chunk_tokens)
        mask = mask.reshape(nc, chunk_tokens)

        @jax.checkpoint
        def body(carry, xs):
            hc, yc, mc = xs
            wm = wv.T if transpose_weight else wv
            logits = (hc @ wm.astype(hc.dtype)).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(
                logits, yc[:, None].astype(jnp.int32), axis=1)[:, 0]
            return carry + jnp.sum((lse - tgt) * mc), None

        total, _ = jax.lax.scan(body, jnp.float32(0),
                                (hs, ys_safe, mask))
        return total / jnp.maximum(mask.sum(), 1.0)

    return apply("chunked_ce", f, hidden, labels, weight)


def causal_lm_loss(logits, labels, ignore_index: int = -100):
    """Next-token cross entropy of dense [B, S, V] logits, the dense
    counterpart of ``chunked_softmax_cross_entropy``
    (``models.lm_head.next_token_loss`` chooses between them). The causal
    shift is made on the LABELS (``labels[:, 1:]`` with one ``ignore_index`` column
    appended), so the logits are only reshaped to [B*S, V], a bitcast:
    ``logits[:, :-1]`` would copy them into B*(S-1) rows, which no tile
    divides. Same sum over the same B*(S-1) positions, same denominator."""
    def shift(y):
        last = jnp.full((y.shape[0], 1), ignore_index, y.dtype)
        return jnp.concatenate([y[:, 1:], last], axis=1).reshape(-1)

    v = logits.shape[-1]
    return cross_entropy(logits.reshape([-1, v]),
                         apply_nodiff("shift_labels", shift, labels),
                         ignore_index=ignore_index)


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean", name=None):
    def f(a, y):
        if log_input:
            loss = jnp.exp(a) - y * a
        else:
            loss = a - y * jnp.log(a + epsilon)
        if full:
            stirling = y * jnp.log(y + epsilon) - y + 0.5 * jnp.log(2 * jnp.pi * (y + epsilon))
            loss = loss + jnp.where(y > 1, stirling, 0.0)
        return _reduce(loss, reduction)
    return apply("poisson_nll", f, input, label)
