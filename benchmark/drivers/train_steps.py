"""Back-to-back training steps on seeded random batches, the loss read
after every step.

Set-up builds ONE object, the compiled step with its state, and drives it
through its first three steps on rows that all differ, through the same
call and feed as the window; the readings the reference is later held
against (each step's loss, the first gradient's norm per leaf out of the
optimizer's state after one step, the parameters' change per leaf after
three) are taken there, before step 4 consumes the state. The window then
continues with that same object."""
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import manifest, systems, traffic, weights as W
from ._serving import span

CHECK_STEPS = 3


def _norm(x):
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def feed(mix, vocab, seed, step):
    return traffic.train_batch(vocab, mix["batch"], mix["seq"], seed, step)


def setup(cfg: dict, mix: dict, seed: int, log):
    trainer = systems.Trainer(cfg, seed)
    vocab = cfg["model"]["vocab_size"]
    b1 = cfg["optimizer"]["beta1"]
    readings = {"loss": [], "grad_norm": {}, "change_norm": {}}
    for step in range(CHECK_STEPS):
        t0 = time.perf_counter()
        loss = float(trainer(feed(mix, vocab, seed, step)))
        log(f"step {step + 1}: loss {loss:.6f} in "
            f"{time.perf_counter() - t0:.2f} s")
        readings["loss"].append(loss)
        if step == 0:
            # m after one step is (1 - beta1) * g: the gradient as the
            # optimizer got it
            for name in trainer.leaves:
                readings["grad_norm"][name] = \
                    _norm(trainer.opt_leaf("m", name)) / (1.0 - b1)
    seeded = W.Leaves(manifest.family_of(cfg), cfg, seed)
    for name in seeded.shapes:
        start = seeded.make(name).astype(jnp.float32)
        now = trainer.opt_leaf("master", name)
        now = trainer.leaves[name]._value if now is None else now
        readings["change_norm"][name] = _norm(now - start)
    return trainer, {"readings": readings}


def release(trainer):
    trainer.opt._state = None
    for p in trainer.leaves.values():
        p._replace(jnp.zeros((), p._value.dtype))
    trainer.step._compiled = None
    jax.clear_caches()
    gc.collect()


def run(trainer, mix: dict, vocab: int, seed: int, seconds: float, hooks):
    step, losses = CHECK_STEPS, []
    tokens = mix["batch"] * mix["seq"]
    hooks.window_open(trainer)
    t0 = hooks.clock()
    while True:
        hooks.tick(hooks.clock() - t0, step_boundary=True)
        with span("input"):
            ids = feed(mix, vocab, seed, step)
        with span("step"):
            loss = trainer(ids)
        with span("loss_read"):
            losses.append(float(jax.block_until_ready(loss)))
        step += 1
        if hooks.clock() - t0 >= seconds:
            break
    elapsed = hooks.clock() - t0
    hooks.window_close(trainer)
    n = len(losses)
    bad = sum(not np.isfinite(x) for x in losses)
    return {"attempted": n, "failed": bad, "window": (t0, t0 + elapsed),
            "losses": losses,
            "end_to_end": {"train_tokens_per_s": n * tokens / elapsed},
            "clock": {}}


def check_numbers(cfg: dict, mix: dict, seed: int, res: dict, setup: dict):
    """Once the trainer is freed: the reference follows the same first
    steps on the same batches."""
    from .. import check
    steps = int(mix.get("check_steps", CHECK_STEPS))
    batches = [feed(mix, cfg["model"]["vocab_size"], seed, s)
               for s in range(steps)]
    program = dict(setup["readings"])
    if steps < CHECK_STEPS:
        raise ValueError("the change is read after three steps; the "
                         "reference has to follow all of them")
    reference = check.reference_train_readings(cfg, seed, batches)
    return check.train_numbers(program, reference)
