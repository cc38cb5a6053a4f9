"""jit.TrainStep's compiled programs are two stages: every trainable
leaf's gradient is finished, then whatever consumes them starts.

The seam is one ``jax.lax.optimization_barrier`` over the gradients at
the end of the closure that the plain step and both gradient-merge
programs share. It is the identity on values: a step the test composes
itself from the model's loss, ``jax.value_and_grad`` and
``optimizer.update``, with no barrier, gives the same bits. A tiny Llama
throughout: the model brings no barrier of its own.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.framework.core import default_generator, no_grad, with_rng_key
from paddle_tpu.jit import functional_call
from paddle_tpu.models import LlamaForCausalLM, llama_tiny
from paddle_tpu.utils import telemetry

COUNTER = "train_step.grad_barrier_leaves"
BARRIER = re.compile(r"^.*stablehlo\.optimization_barrier.*$", re.M)


def _model(dtype=jnp.float32, freeze=()):
    paddle.seed(7)
    model = LlamaForCausalLM(llama_tiny(num_hidden_layers=1))
    for name, p in model.named_parameters():
        p._replace(p._value.astype(dtype))
        if name in freeze:
            p.stop_gradient = True
    return model


def _batch(i=0):
    ids = np.random.default_rng(100 + i).integers(0, 512, size=(2, 16))
    return jnp.asarray(ids, jnp.int32)


def _step(model, opt, **kw):
    return paddle.jit.TrainStep(
        model, lambda out, lab: model.loss(out, lab), opt, **kw)


def _adamw(model):
    return optimizer.AdamW(learning_rate=1e-2, weight_decay=0.1,
                           parameters=model.parameters())


def _sgd(model):
    return optimizer.SGD(learning_rate=0.5, parameters=model.parameters())


def _key(i):
    return jax.random.fold_in(default_generator._key, i)


# -- (a) one barrier, over the trainable leaves and nothing else --------------

def _lowered_text(step, program, ids):
    """The StableHLO of one of the programs ``step`` would build."""
    opt = step.optimizer
    opt._state = opt.init_state([p._value for p in opt._parameter_list])
    p = [t._value for t in step._p_tensors]
    b = [t._value for t in step._b_tensors]
    lr = jnp.asarray(opt.get_lr(), jnp.float32)
    batch = (_key(0), (ids,), (ids,))
    if program == "step":
        return step._build().lower(p, b, opt._state, lr, *batch).as_text()
    accum = step._init_gm_accum()
    accum_fn, apply_fn = step._build_gm()
    if program == "accum_step":
        return accum_fn.lower(p, b, accum, *batch).as_text()
    return apply_fn.lower(p, b, opt._state, lr, accum, *batch).as_text()


@pytest.mark.parametrize("freeze", [(), ("model.embed_tokens.weight",)],
                         ids=["all_trainable", "one_frozen"])
@pytest.mark.parametrize("program", ["step", "accum_step", "apply_step"])
def test_one_barrier_over_the_trainable_gradients(program, freeze):
    model = _model(freeze=freeze)
    names = [n for n, _ in model.named_parameters()]
    assert set(freeze) <= set(names)
    trainable = len(names) - len(freeze)
    step = _step(model, _adamw(model),
                 gradient_merge=1 if program == "step" else 2)
    text = _lowered_text(step, program, _batch())
    [line] = BARRIER.findall(text)
    operands = line.split(" : ")[-1]
    assert operands.count("tensor<") == trainable
    # gradients only: the scalar loss is not under it
    assert "tensor<f32>" not in operands


# -- (b) the identity on values -----------------------------------------------

def _reference_programs(model, opt, k):
    """What TrainStep computes, composed here with no barrier: a plain
    step, or gradient merge's accumulate and apply steps."""
    tensors = [p for _, p in model.named_parameters()]
    buffers = [b for _, b in model.named_buffers() if b is not None]
    assert all(not p.stop_gradient for p in tensors)
    assert [id(p) for p in opt._parameter_list] == [id(p) for p in tensors]
    b_arrays = [b._value for b in buffers]

    def loss_and_grads(params, key, ids):
        def loss_f(ps):
            with with_rng_key(key):
                out, _ = functional_call(model, ps, b_arrays, (ids,))
            with with_rng_key(jax.random.fold_in(key, 777)), no_grad():
                loss = model.loss(paddle.Tensor(out), paddle.Tensor(ids))
            return loss._value.astype(jnp.float32)
        return jax.value_and_grad(loss_f)(list(params))

    def plain(params, state, lr, key, ids):
        loss, grads = loss_and_grads(params, key, ids)
        return (loss,) + tuple(opt.update(params, grads, state, lr))

    def accumulate(params, accum, key, ids):
        loss, grads = loss_and_grads(params, key, ids)
        return loss, [a + g.astype(jnp.float32)
                      for a, g in zip(accum, grads)]

    def apply(params, state, lr, accum, key, ids):
        loss, grads = loss_and_grads(params, key, ids)
        merged = [((a + g.astype(jnp.float32)) / k).astype(g.dtype)
                  for a, g in zip(accum, grads)]
        new_params, new_state = opt.update(params, merged, state, lr)
        return loss, new_params, new_state, [jnp.zeros_like(a) for a in accum]

    return jax.jit(plain), jax.jit(accumulate), jax.jit(apply)


def _reference_run(model, opt, k, steps):
    plain, accumulate, apply = _reference_programs(model, opt, k)
    params = [p._value for _, p in model.named_parameters()]
    state = opt.init_state(params)
    lr = jnp.asarray(opt.get_lr(), jnp.float32)
    accum = [jnp.zeros(p.shape, jnp.float32) for p in params]
    losses = []
    for i in range(steps):
        if k == 1:
            loss, params, state = plain(params, state, lr, _key(i), _batch(i))
        elif (i + 1) % k:
            loss, accum = accumulate(params, accum, _key(i), _batch(i))
        else:
            loss, params, state, accum = apply(params, state, lr, accum,
                                               _key(i), _batch(i))
        losses.append(np.asarray(loss))
    return losses, params, state, accum


def _bits(x):
    a = np.asarray(x)
    return a.view(np.uint16) if a.dtype == jnp.bfloat16 else a


def _assert_same_bits(got, want, what):
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) and got, what
    for i, (g, w) in enumerate(zip(got, want)):
        assert np.asarray(g).dtype == np.asarray(w).dtype, (what, i)
        np.testing.assert_array_equal(_bits(g), _bits(w),
                                      err_msg=f"{what}[{i}]")


@pytest.mark.parametrize("case,dtype,make_opt,k", [
    ("adamw_bf16_master", jnp.bfloat16, _adamw, 1),
    ("sgd_float32", jnp.float32, _sgd, 1),
    ("adamw_bf16_gradient_merge_2", jnp.bfloat16, _adamw, 2),
])
def test_three_steps_equal_a_step_composed_without_the_barrier(
        case, dtype, make_opt, k):
    steps = 3
    ref_model = _model(dtype)
    want_losses, want_params, want_state, want_accum = _reference_run(
        ref_model, make_opt(ref_model), k, steps)

    model = _model(dtype)
    opt = make_opt(model)
    step = _step(model, opt, gradient_merge=k)
    losses = [np.asarray(step(paddle.to_tensor(_batch(i)),
                              paddle.to_tensor(_batch(i)))._value)
              for i in range(steps)]

    if dtype == jnp.bfloat16:
        assert opt._state["master"][0].dtype == jnp.float32
    _assert_same_bits(losses, want_losses, "loss")
    _assert_same_bits([p._value for _, p in model.named_parameters()],
                      want_params, "parameter")
    _assert_same_bits(opt._state, want_state, "optimizer state")
    if k > 1:       # step 3 of 3 accumulated: the accumulators are live
        _assert_same_bits(step._gm_accum, want_accum, "accumulator")
    # the reference left its model's own tensors alone: the start
    assert not np.array_equal(_bits(want_params[0]),
                              _bits(ref_model.parameters()[0]._value))


# -- (c) the counter that says it engaged -------------------------------------

@pytest.mark.parametrize("freeze", [(), ("model.norm.weight",)],
                         ids=["all_trainable", "one_frozen"])
def test_counter_reads_the_leaf_count_after_the_first_call(freeze):
    metrics = telemetry.default_tracer().metrics
    with metrics._lock:
        metrics.counters.pop(COUNTER, None)
    model = _model(freeze=freeze)
    trainable = len(model.parameters()) - len(freeze)
    step = _step(model, _sgd(model))
    assert metrics.value(COUNTER) is None       # nothing traced yet
    ids = paddle.to_tensor(_batch())
    step(ids, ids)
    assert metrics.value(COUNTER) == trainable
    step(ids, ids)                              # no second trace
    assert metrics.value(COUNTER) == trainable
