"""A driver dropped in as a file (a test fixture): ``train_steps`` with
the losses read once, when the window closes, instead of after every
step. Set-up, release and the comparison are ``train_steps``' own."""
import jax
import numpy as np

from .train_steps import (CHECK_STEPS, check_numbers, feed,  # noqa: F401
                          release, setup)


def run(trainer, mix: dict, vocab: int, seed: int, seconds: float, hooks):
    step, losses = CHECK_STEPS, []
    hooks.window_open(trainer)
    t0 = hooks.clock()
    while hooks.clock() - t0 < seconds and len(losses) < mix["max_steps"]:
        losses.append(trainer(feed(mix, vocab, seed, step)))
        step += 1
    losses = [float(x) for x in jax.block_until_ready(losses)]
    elapsed = hooks.clock() - t0
    hooks.window_close(trainer)
    return {"attempted": len(losses),
            "failed": sum(not np.isfinite(x) for x in losses),
            "window": (t0, t0 + elapsed), "losses": losses,
            "end_to_end": {"train_tokens_per_s": len(losses) * mix["batch"]
                           * mix["seq"] / elapsed},
            "clock": {}}
