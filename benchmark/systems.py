"""The system under test, built the way a user builds it.

This module and the family modules (``benchmark/families``) are the only
ones of the benchmark that import the program. Serving: the family's
decoder, fed the benchmark's seeded leaves through the program's loader
entry (quantised by the program as they arrive), behind
``ServingEngine``. Training: the family's trainable model +
``optimizer.AdamW`` + ``jit.TrainStep``, with the same leaves assigned
to its parameters.
"""
import time

import numpy as np

from . import manifest, weights as W


# -- serving ------------------------------------------------------------------

def build_engine(cfg: dict, seed: int):
    """(engine, seconds to make and quantise the weights)."""
    from paddle_tpu.inference import ServingEngine
    family = manifest.family_of(cfg)
    t0 = time.perf_counter()
    dec = family.build_decoder(cfg, W.Leaves(family, cfg, seed).make)
    t_weights = time.perf_counter() - t0
    opts = dict(cfg["engine"])
    if "prompt_buckets" in opts:
        opts["prompt_buckets"] = tuple(opts["prompt_buckets"])
    eng = ServingEngine(dec, seed=W.model_seed(seed), **opts)
    return eng, t_weights


def greedy(max_new_tokens: int):
    from paddle_tpu.inference import SamplingParams
    return SamplingParams(max_new_tokens=int(max_new_tokens))


def ragged_program_set(eng):
    """Every (T, W) the ragged scheduler can dispatch for greedy
    requests: T ministeps (1 while nothing decodes, else the chunk
    rung) by W padded rows. Mixed chunks carry at most max_batch decode
    columns plus ceil(prefill budget / T) prefill columns; pure-prefill
    chunks (T = 1) carry at most the idle cap."""
    cap = eng._ragged_cap
    idle = max(cap, eng._ragged_idle_cap)
    out = []
    for T in sorted(set(eng.chunks)):
        rows = eng.max_b + -(-cap // T)
        out += [(T, w) for w in eng._widths_up_to(rows)]
    out += [(1, w) for w in eng._widths_up_to(idle)]
    return sorted(set(out))


def ragged_operands(eng, T, Wd):
    """The operands after (weights, k, v) of one all-scratch ragged
    chunk, as ``ServingEngine.warmup_programs`` builds them."""
    import jax
    mb, mp, aj = eng.max_b, eng.dec.max_pages, eng._aj
    z2 = np.zeros((T, Wd), np.int32)
    return (eng._zeros_toks(T, Wd), aj(np.zeros(Wd, np.int32)),
            aj(np.zeros(Wd, np.int32)), aj(np.ones(Wd, bool)),
            aj(np.zeros(Wd, np.int32)), aj(z2), aj(z2),
            aj(np.full((T, Wd), eng._scratch_slot, np.int32)),
            aj(np.full((T, Wd), mb, np.int32)), aj(z2),
            aj(np.zeros((T, Wd), bool)),
            aj(np.full((mb + 1, mp), eng._scratch_block, np.int32)),
            aj(np.zeros((T, Wd), np.float32)),
            eng._replicated(jax.random.split(jax.random.PRNGKey(0), T)))


def warm_ragged(eng, pairs, log=None):
    """Compile (or load from the cache) the plain ragged step program at
    each (T, W), by direct invocation on scratch rows exactly as
    ``ServingEngine.warmup_programs`` does it, but for the one program
    family greedy traffic dispatches: that method also compiles the
    rich-sampling twin of every shape. Returns [(T, W, seconds)]."""
    import jax
    cache = eng.dec.cache
    took = []
    for T, Wd in pairs:
        tail = ragged_operands(eng, T, Wd)
        t0 = time.perf_counter()
        toks, cache.k, cache.v = eng._ragged_j(
            eng.dec.weights, cache.k, cache.v, *tail)
        jax.block_until_ready(toks)
        t1 = time.perf_counter()
        n_new, _ = eng.compile_watch.observe(
            eng._ragged_j, t0, t1, (eng.dec.weights, cache.k, cache.v) + tail)
        eng.program_compiles += n_new
        took.append((T, Wd, t1 - t0))
        if log:
            log(f"program ragged[T={T},W={Wd}] {t1 - t0:.2f} s")
    return took


# -- training -----------------------------------------------------------------

class Trainer:
    """One compiled step with its state. ``leaves`` maps the benchmark's
    leaf names to the model's live parameters."""

    def __init__(self, cfg: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu import optimizer
        self.paddle = paddle
        paddle.seed(W.model_seed(seed))
        family = manifest.family_of(cfg)
        model, param_of = family.build_trainable(cfg)
        named = dict(model.named_parameters())
        seeded = W.Leaves(family, cfg, seed)
        self.leaves = {}
        for name, shape in seeded.shapes.items():
            p = named.pop(param_of[name])
            if tuple(p.shape) != shape:
                raise ValueError(f"{name}: model has {tuple(p.shape)}, "
                                 f"config says {shape}")
            p._replace(seeded.make(name))
            self.leaves[name] = p
        if named:
            raise ValueError(f"parameters without a seeded leaf: "
                             f"{sorted(named)}")
        o = cfg["optimizer"]
        self.opt = optimizer.AdamW(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"],
            weight_decay=o["weight_decay"], parameters=model.parameters())
        self.model = model
        self.step = paddle.jit.TrainStep(
            model, lambda out, lab: model.loss(out, lab), self.opt)

    @property
    def compile_watch(self):
        """The step's own count of compiles, where ``Hooks.compiles``
        looks for it."""
        return self.step.compile_watch

    def __call__(self, ids):
        """One step on a [batch, seq] int32 array; returns the loss, not
        yet waited for."""
        t = self.paddle.to_tensor(ids)
        return self.step(t, t)._value

    def opt_leaf(self, key: str, name: str):
        """One leaf of the optimizer's state ('m', 'v', 'master'); None
        where the optimizer keeps none (no master copy of a float32
        parameter)."""
        leaves = self.opt._state.get(key)
        if leaves is None:
            return None
        idx = {id(p): i for i, p in enumerate(self.opt._parameter_list)}
        return leaves[idx[id(self.leaves[name])]]


def reseed_engine(eng, cfg: dict, seed: int):
    """Give a built engine the weights of another seed (its programs take
    the weights as an argument, so nothing compiles again). Only
    ``readings.py`` does this, to read many seeds in one process; a run of
    the benchmark builds its engine from its own seed."""
    import gc
    import jax.numpy as jnp
    cache = eng.dec.cache
    # the pool goes too while the weights are made: quantising the head
    # needs a gigabyte of float32 that the pool leaves no room for
    planes = [(a.shape, a.dtype) for a in cache.k]
    eng.dec.weights = cache.k = cache.v = None
    gc.collect()
    family = manifest.family_of(cfg)
    eng.dec.weights = family.build_decoder(
        cfg, W.Leaves(family, cfg, seed).make, num_blocks=2).weights
    cache.k = [jnp.zeros(s, d) for s, d in planes]
    cache.v = [jnp.zeros(s, d) for s, d in planes]
