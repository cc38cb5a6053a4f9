"""The comparisons that decide ``correct``: the timed path's own output
against the plain reference, each number beside a limit of its own.

Limits are data, ``benchmark/limits/<workload>.json``: {"<number>":
{"limit": x, "lower": ..., "upper": ...}} with the readings each was set
from. A number without an entry there is reported and not judged.

**What a family's plain reference has** (``benchmark/reference/<name>.py``,
named by the family module's ``REFERENCE``). It imports nothing of the
program, makes its weights again from the seed (``benchmark.weights``)
and computes in float32 at ``highest``. ``precision`` is ``"stated"``, or
``"lower"`` for the control: the same equations in the nearest precision
below the one the configuration states, which only ``readings.py`` and
the tests ask for.

``train_params(cfg, seed, precision="stated")``
    {leaf name: float32 array}, every leaf of the family's list, as the
    trainer's parameters start.
``loss_and_grads(params, batch, cfg, precision="stated", rows=None)``
    (loss as a float, {leaf name: gradient}): the mean next-token loss of
    a [batch, seq] array of token ids and its gradients, in blocks that
    fit beside the parameters; ``rows`` limits the mean to those rows (a
    planted fault).
``sequence_logits(cfg, seed, seqs, positions, precision="stated")``,
    where the family serves: one [len(positions[i]), vocab] float32
    array per sequence, the next-token logits at those positions.

AdamW and the norms per leaf are no family's: ``benchmark/reference/
adamw.py``.
"""
import json
import os
import statistics

import numpy as np

from . import manifest
from .reference import adamw


def reference_of(cfg: dict):
    """The plain reference of a configuration's family."""
    return manifest.module("reference", manifest.family_of(cfg).REFERENCE)


def load_limits(workload: str) -> dict:
    with open(os.path.join(manifest.DATA, "limits",
                           f"{workload}.json")) as f:
        return {k: v["limit"] for k, v in json.load(f)["numbers"].items()}


def judge(numbers: dict, limits: dict):
    """(correct, {name: {"value", "limit"}}): every limited number must be
    there, finite and at or under its limit."""
    out, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        out[name] = {"value": value, "limit": limit}
    for name, value in numbers.items():
        out.setdefault(name, {"value": value, "limit": None})
    return ok, out


# -- serving ------------------------------------------------------------------

def served_sequences(sample):
    """(sequences, positions, served tokens): each sampled request's
    prompt followed by its served tokens, and the positions whose
    next-token logits decided those tokens."""
    seqs, pos, toks = [], [], []
    for rec in sample:
        n, k = len(rec.prompt), len(rec.tokens)
        seqs.append(np.concatenate([rec.prompt, rec.tokens]))
        pos.append(np.arange(n - 1, n - 1 + k))
        toks.append(np.asarray(rec.tokens))
    return seqs, pos, toks


def gaps_below_best(logits, tokens):
    """How far each token's reference logit lies below the reference's
    best, per position."""
    logits = np.asarray(logits, np.float32)
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]


def serve_numbers(cfg: dict, seed: int, sample) -> dict:
    """The widest gap by which a served token's logit lies below the
    reference's best, over every served token of the sample."""
    if not sample:
        return {"served_tokens_checked": 0}
    seqs, pos, toks = served_sequences(sample)
    logits = reference_of(cfg).sequence_logits(cfg, seed, seqs, pos)
    gaps = np.concatenate([gaps_below_best(l, t)
                           for l, t in zip(logits, toks)])
    return {"served_logit_gap_max": float(gaps.max()),
            "served_logit_gap_mean": float(gaps.mean()),
            "served_tokens_off_best": int((gaps > 0).sum()),
            "served_tokens_checked": int(gaps.size)}


# -- training -----------------------------------------------------------------

def reference_train_readings(cfg: dict, seed: int, batches,
                             precision: str = "stated", rows=None) -> dict:
    """The reference's own loss of each step, first gradient norm per leaf
    and change of every leaf after the steps."""
    ref = reference_of(cfg)
    params = ref.train_params(cfg, seed, precision)
    start = ref.train_params(cfg, seed)
    state = {"m": {}, "v": {}}
    out = {"loss": [], "grad_norm": {}, "change_norm": {}}
    for t, batch in enumerate(batches, 1):
        loss, grads = ref.loss_and_grads(params, batch, cfg, precision, rows)
        out["loss"].append(loss)
        if t == 1:
            out["grad_norm"] = adamw.leaf_norms(grads)
        params, state = adamw.adamw_step(params, grads, state,
                                         cfg["optimizer"], t)
    out["change_norm"] = adamw.leaf_norms(
        {k: params[k] - start[k] for k in params})
    return out


def train_numbers(program: dict, reference: dict) -> dict:
    """Gaps between the program's readings and the reference's.

    loss: the widest relative gap over the steps. Norms: by the worst
    leaf, the gap between the two NORMS (not the norm of a difference)
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Leaves whose reference gradient is under a
    thousandth of the median leaf's move under Adam by round-off alone
    and are left out of the change."""
    ref_g = reference["grad_norm"]
    med_g = statistics.median(ref_g.values())
    med_c = statistics.median(reference["change_norm"].values())
    steps = min(len(program["loss"]), len(reference["loss"]))
    loss_gap = max(abs(program["loss"][i] - reference["loss"][i])
                   / abs(reference["loss"][i]) for i in range(steps))
    grad = {k: abs(program["grad_norm"][k] - ref_g[k]) / max(ref_g[k], med_g)
            for k in ref_g}
    moved = [k for k in ref_g if ref_g[k] >= 1e-3 * med_g]
    change = {k: abs(program["change_norm"][k] - reference["change_norm"][k])
              / max(reference["change_norm"][k], med_c) for k in moved}
    worst_g, worst_c = max(grad, key=grad.get), max(change, key=change.get)
    return {"loss_rel_gap_max": float(loss_gap),
            "first_loss_rel_gap": float(
                abs(program["loss"][0] - reference["loss"][0])
                / abs(reference["loss"][0])),
            "grad_norm_gap_worst_leaf": float(grad[worst_g]),
            "change_norm_gap_worst_leaf": float(change[worst_c]),
            "_worst_leaves": {"grad": worst_g, "change": worst_c}}
