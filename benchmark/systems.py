"""The system under test, built the way a user builds it.

The only module of the benchmark that imports the program. Serving:
``PagedLlamaDecoder.from_weight_loader`` (the benchmark's seeded leaves,
quantised by the program as they arrive) behind ``ServingEngine``.
Training: ``LlamaForCausalLM`` + ``optimizer.AdamW`` + ``jit.TrainStep``
with the same leaves assigned to its parameters.
"""
import time

import numpy as np

from . import weights as W


def llama_config(cfg: dict, **extra):
    from paddle_tpu.models import LlamaConfig
    m = cfg["model"]
    if m["hidden_size"] != m["num_attention_heads"] * m["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "num_attention_heads; this config disagrees")
    if m.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    return LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=m["torch_dtype"], **extra)


# -- serving ------------------------------------------------------------------

def build_engine(cfg: dict, seed: int):
    """(engine, seconds to make and quantise the weights)."""
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
    lcfg = llama_config(cfg)
    dtype = cfg["model"]["torch_dtype"]

    def load(name, shape):
        return W.make_leaf(seed, name, shape, dtype, cfg["init_scale"])

    t0 = time.perf_counter()
    dec = PagedLlamaDecoder.from_weight_loader(lcfg, load, **cfg["decoder"])
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

_TRAIN_NAMES = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
                "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
                "wg": "mlp.gate_proj", "wu": "mlp.up_proj",
                "wd": "mlp.down_proj", "ln1": "input_layernorm",
                "ln2": "post_attention_layernorm"}


def train_param_name(leaf: str) -> str:
    """The benchmark's leaf name -> LlamaForCausalLM's parameter name."""
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "norm":
        return "model.norm.weight"
    if leaf == "head":
        return "lm_head.weight"
    _, i, k = leaf.split(".")
    return f"model.layers.{i}.{_TRAIN_NAMES[k]}.weight"


class Trainer:
    """One compiled step with its state. ``leaves`` maps the benchmark's
    leaf names to the model's live parameters."""

    def __init__(self, cfg: dict, seed: int):
        import paddle_tpu as paddle
        from paddle_tpu import optimizer
        from paddle_tpu.models import LlamaForCausalLM
        self.paddle = paddle
        paddle.seed(W.model_seed(seed))
        lcfg = llama_config(cfg, **cfg["trainer"])
        model = LlamaForCausalLM(lcfg)
        named = dict(model.named_parameters())
        dtype = cfg["model"]["torch_dtype"]
        self.leaves = {}
        for name, shape in W.leaf_shapes(cfg["model"]):
            p = named.pop(train_param_name(name))
            if tuple(p.shape) != tuple(shape):
                raise ValueError(f"{name}: model has {tuple(p.shape)}, "
                                 f"config says {shape}")
            p._replace(W.make_leaf(seed, name, shape, dtype,
                                   cfg["init_scale"]))
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
    from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
    cache = eng.dec.cache
    # the pool goes too while the weights are made: quantising the head
    # needs a gigabyte of float32 that the pool leaves no room for
    planes = [(a.shape, a.dtype) for a in cache.k]
    eng.dec.weights = cache.k = cache.v = None
    gc.collect()
    dtype = cfg["model"]["torch_dtype"]

    def load(name, shape):
        return W.make_leaf(seed, name, shape, dtype, cfg["init_scale"])

    small = dict(cfg["decoder"], num_blocks=2)
    eng.dec.weights = PagedLlamaDecoder.from_weight_loader(
        llama_config(cfg), load, **small).weights
    cache.k = [jnp.zeros(s, d) for s, d in planes]
    cache.v = [jnp.zeros(s, d) for s, d in planes]
