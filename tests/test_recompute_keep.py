"""``recompute(keep=)``: a rematerialised region keeps the values its
kernels name. The flash kernels name their output and its log-sum-exp
(``FLASH_KEEP``), so a region that keeps them holds the forward kernel once
in its gradient, where a bare ``jax.checkpoint`` holds it twice; the default
keeps nothing and is the program it was. Interpret mode on the CPU: counts
and bits, no speeds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.distributed.fleet import recompute
from paddle_tpu.framework.core import apply
from paddle_tpu.jit import _wrap_tree, functional_call
from paddle_tpu.models import (LlamaForCausalLM, SmallThinkerForCausalLM,
                               llama_tiny, smallthinker_tiny)
from paddle_tpu.ops.pallas import flash_attention as pf
from paddle_tpu.utils import telemetry

HEADS, KV_HEADS, HEAD, HIDDEN, SEQ, BLOCK = 4, 2, 8, 32, 128, 32


class _AttnLayer(nn.Layer):
    """A layer's attention half at toy widths: four projections around the
    Pallas kernels, run plainly (``keep=None``) or as one rematerialised
    region that keeps ``keep``."""

    def __init__(self, attend, keep):
        super().__init__()
        lin = lambda n_in, n_out: nn.Linear(n_in, n_out, bias_attr=False)
        self.q_proj = lin(HIDDEN, HEADS * HEAD)
        self.k_proj = lin(HIDDEN, KV_HEADS * HEAD)
        self.v_proj = lin(HIDDEN, KV_HEADS * HEAD)
        self.o_proj = lin(HEADS * HEAD, HIDDEN)
        self.attend = attend
        self.keep = keep

    def _block(self, x):
        def f(xa, wq, wk, wv, wo):
            b, s, _ = xa.shape
            q = (xa @ wq).reshape(b, s, HEADS, HEAD)
            k = (xa @ wk).reshape(b, s, KV_HEADS, HEAD)
            v = (xa @ wv).reshape(b, s, KV_HEADS, HEAD)
            return xa + self.attend(q, k, v).reshape(b, s, HEADS * HEAD) @ wo
        return apply("attn_layer", f, x, self.q_proj.weight,
                     self.k_proj.weight, self.v_proj.weight,
                     self.o_proj.weight)

    def forward(self, x):
        if self.keep is None:
            return self._block(x)
        from paddle_tpu.models.llama import _LayerFn
        return recompute(_LayerFn(self), x, keep=self.keep)


def _plain(window):
    return lambda q, k, v: pf.flash_attention_pallas(
        q, k, v, True, None, BLOCK, BLOCK, window)


def _segmented(q, k, v):
    seg = jnp.asarray(np.arange(SEQ)[None] // 48, jnp.int32).repeat(
        q.shape[0], 0)
    return pf.flash_attention_pallas_segmented(q, k, v, seg, seg, True,
                                               None, BLOCK, BLOCK)


def _grad_of(attend, keep):
    """(the jaxpr of the layer's gradient w.r.t. its input and its four
    weights, the five gradients)."""
    paddle.seed(0)
    layer = _AttnLayer(attend, keep)
    x = jnp.asarray(np.random.RandomState(1).randn(2, SEQ, HIDDEN) * 0.5,
                    jnp.float32)
    params = [p._value for p in layer.parameters()]

    def loss(x, params):
        out, _ = functional_call(layer, params, [], (_wrap_tree(x),))
        return jnp.sum(jnp.sin(out))
    grad = jax.grad(loss, (0, 1))
    dx, dparams = grad(x, params)
    return str(jax.make_jaxpr(grad)(x, params)), [dx, *dparams]


def _same_bits(got, want):
    assert len(got) == len(want) == 5
    for a, b in zip(got, want):
        assert (np.asarray(a) == np.asarray(b)).all()


# (a): with and without a window
@pytest.mark.parametrize("window,stem", [(None, "flash_fwd"),
                                         (40, "flash_win_fwd")])
def test_a_region_that_keeps_the_kernels_outputs_runs_the_forward_kernel_once(
        window, stem):
    attend = _plain(window)
    plain_text, plain = _grad_of(attend, None)
    bare_text, bare = _grad_of(attend, ())
    kept_text, kept = _grad_of(attend, pf.FLASH_KEEP)
    call = f"name={stem}\n"
    assert plain_text.count(call) == 1
    assert bare_text.count(call) == 2       # the forward pass, and again
    assert kept_text.count(call) == 1
    # the backward kernels are there once in each
    back = f"name={stem.replace('fwd', 'bwd_dq')}\n"
    assert plain_text.count(back) == bare_text.count(back) \
        == kept_text.count(back) >= 1
    _same_bits(bare, plain)
    _same_bits(kept, plain)


# (b): the default is the program it was, and a name nothing carries
def test_no_keep_passes_no_policy_and_an_unknown_name_keeps_nothing(
        monkeypatch):
    asked = []
    checkpoint = jax.checkpoint
    monkeypatch.setattr(jax, "checkpoint", lambda fn, **kw: (
        asked.append(kw), checkpoint(fn, **kw))[1])
    attend = _plain(None)
    bare_text, bare = _grad_of(attend, ())
    assert asked and all(kw == {} for kw in asked)
    del asked[:]
    other_text, other = _grad_of(attend, ("no_value_has_this_name",))
    assert asked and all(set(kw) == {"policy"} for kw in asked)
    monkeypatch.undo()
    # the jaxpr a bare jax.checkpoint builds, to the letter
    assert "policy=None" in bare_text
    assert "policy=None" not in other_text
    assert other_text.count("name=flash_fwd\n") == 2
    _same_bits(other, bare)
    _same_bits(bare, _grad_of(attend, None)[1])


# (c): the segmented twin names its outputs too
def test_the_segmented_entry_keeps_its_outputs_too():
    plain_text, plain = _grad_of(_segmented, None)
    bare_text, bare = _grad_of(_segmented, ())
    kept_text, kept = _grad_of(_segmented, pf.FLASH_KEEP)
    counts = [t.count("name=flash_fwd\n")
              for t in (plain_text, bare_text, kept_text)]
    assert counts == [1, 2, 1]
    _same_bits(bare, plain)
    _same_bits(kept, plain)


def test_the_names_lower_to_nothing_outside_a_region():
    """Outside a ``jax.checkpoint`` with a policy a name is an identity:
    the lowered text of the kernel's gradient holds no trace of it but the
    identity ``name`` equation in the jaxpr."""
    q = jnp.ones((1, SEQ, HEADS, HEAD), jnp.float32)
    k = v = jnp.ones((1, SEQ, KV_HEADS, HEAD), jnp.float32)
    grad = jax.grad(lambda q, k, v: jnp.sum(_plain(None)(q, k, v)))
    assert "flash_out" in str(jax.make_jaxpr(grad)(q, k, v))
    text = jax.jit(grad).lower(q, k, v).as_text()
    assert "flash_out" not in text and "flash_lse" not in text
    assert "optimization_barrier" not in text


# (d): the counters that say the mechanism engaged
def _counts():
    reg = telemetry.default_tracer().metrics
    return [reg.value(name) or 0 for name in (
        "recompute.regions", "recompute.regions_keeping")]


def _train_once(model):
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 128, (2, 64), dtype=np.int32))
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
    return float(step(ids, ids)._value)


@pytest.mark.parametrize("build,regions,keeping", [
    (lambda: SmallThinkerForCausalLM(smallthinker_tiny(use_recompute=True)),
     4, 4),
    (lambda: SmallThinkerForCausalLM(smallthinker_tiny()), 0, 0),
    (lambda: LlamaForCausalLM(llama_tiny(use_recompute=True,
                                         recompute_granularity="full")),
     2, 0),
], ids=["smallthinker_keeps", "smallthinker_plain", "llama_full_keeps_none"])
def test_the_counters_say_which_regions_a_step_traced(build, regions,
                                                      keeping):
    paddle.seed(0)
    model = build()
    before = _counts()
    assert np.isfinite(_train_once(model))
    grew = [now - was for now, was in zip(_counts(), before)]
    # a TrainStep may trace its step more than once: whole traces only
    traces = grew[0] // regions if regions else 0
    assert traces >= (1 if regions else 0)
    assert grew == [regions * traces, keeping * traces]
