"""LFM2-8B-A1B's decoder on the CPU at a small size: the gated short
convolution against a loop, the sigmoid routing rule with its selection
bias, the expert layer's four shares against the uncut layer, and the
whole model through ``jit.TrainStep`` against the benchmark's plain
reference (``benchmark/reference/lfm2_moe_ref.py``)."""
import copy
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM, lfm2_moe_tiny
from paddle_tpu.ops import moe
from paddle_tpu.ops.short_conv import causal_conv, gated_conv
from paddle_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def close(a, b, tol=1e-5):
    return float(jnp.max(jnp.abs(a - b))) <= tol * (
        1.0 + float(jnp.max(jnp.abs(b))))


# -- the gated short convolution ----------------------------------------------

def loop_gated_conv(bcx, w):
    """C_t * sum_j w_j (B * X)_{t-(L-1)+j}, one token and tap at a time."""
    b_, c_, x_ = np.split(np.asarray(bcx, np.float64), 3, axis=-1)
    z, w = b_ * x_, np.asarray(w, np.float64)
    taps, out = w.shape[0], np.zeros_like(z)
    for t in range(z.shape[1]):
        for j in range(taps):
            if t - (taps - 1) + j >= 0:
                out[:, t] += w[j] * z[:, t - (taps - 1) + j]
    return c_ * out


@pytest.fixture(scope="module")
def conv_operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return (jax.random.normal(ks[0], (3, 12, 24)),       # [b, s, 3c]
            jax.random.normal(ks[1], (3, 8)),            # [L, c]
            jax.random.normal(ks[2], (3, 12, 8)))


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_gated_conv_against_the_loop(conv_operands, taps):
    bcx, _, _ = conv_operands
    w = jax.random.normal(jax.random.PRNGKey(taps), (taps, 8))
    assert close(gated_conv(bcx, w), loop_gated_conv(bcx, w))


def test_gated_convs_own_vjp_against_autodiff_of_the_shifted_products(
        conv_operands):
    """dB, dC, dX and dw (a sum over every token of the batch)."""
    bcx, w, dy = conv_operands

    def plain(bcx, w):
        b_, c_, x_ = jnp.split(bcx, 3, axis=-1)
        return c_ * causal_conv(b_ * x_, w)
    got = jax.grad(lambda a, b: jnp.sum(gated_conv(a, b) * dy), (0, 1))(
        bcx, w)
    want = jax.grad(lambda a, b: jnp.sum(plain(a, b) * dy), (0, 1))(bcx, w)
    for a, b in zip(got, want):
        assert close(a, b)
    assert got[1].shape == w.shape


def test_the_convolution_never_reads_across_the_sequences_of_a_batch(
        conv_operands):
    bcx, w, dy = conv_operands
    whole = gated_conv(bcx, w)
    # a sequence alone gives what it gives in the batch: nothing comes in
    # from the row before it, and zeros stand before its first token
    for r in range(bcx.shape[0]):
        assert close(gated_conv(bcx[r:r + 1], w), whole[r:r + 1], 1e-6)
    first = jnp.split(bcx[:, 0], 3, axis=-1)
    assert close(whole[:, 0], first[1] * w[-1] * first[0] * first[2], 1e-6)
    # and another first sequence changes no output and no input gradient
    # of the others
    other = bcx.at[0].set(bcx[0] * -3.0 + 1.0)
    assert float(jnp.abs(gated_conv(other, w)[1:] - whole[1:]).max()) == 0.0
    g = lambda a: jax.grad(lambda a: jnp.sum(gated_conv(a, w) * dy))(a)
    assert float(jnp.abs(g(other)[1:] - g(bcx)[1:]).max()) == 0.0
    # causal: a later token changes nothing before it
    late = bcx.at[:, 7:].set(0.5)
    assert float(jnp.abs(gated_conv(late, w)[:, :7] - whole[:, :7]).max()) \
        == 0.0


def test_the_layer_trains_its_three_parameters():
    paddle.seed(1)
    layer = nn.GatedShortConv(16, 3)
    assert layer.in_proj.weight.shape == [16, 48]
    assert layer.conv_weight.shape == [3, 16]
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 10, 16)).astype(np.float32))
    before = telemetry.default_tracer().metrics.value("short_conv.layers") \
        or 0
    layer(x).sum().backward()
    assert telemetry.default_tracer().metrics.value("short_conv.layers") \
        == before + 1
    for p in layer.parameters():
        assert float(jnp.abs(p.grad._value).max()) > 0
    assert len(layer.parameters()) == 3         # no bias anywhere


# -- routing ------------------------------------------------------------------

@pytest.fixture(scope="module")
def logits():
    return 2.0 * jax.random.normal(jax.random.PRNGKey(4), (256, 32))


def test_keyes_softmax_rule_is_unchanged_to_the_last_bit(logits):
    """What ``moe_share_forward`` did inline before routing became a step
    of its own."""
    for norm in (True, False):
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, 8)
        gates = top_p / jnp.sum(top_p, -1, keepdims=True) if norm else top_p
        got_i, got = moe.route_softmax(logits, 8, norm)
        assert (np.asarray(got_i) == np.asarray(top_i)).all()
        assert (np.asarray(got) == np.asarray(gates)).all()
    # and it is the rule a caller gets who names none
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (2, 32, 16))
    gw = jax.random.normal(ks[1], (16, 8))
    ws = [0.3 * jax.random.normal(k, s) for k, s in zip(
        ks[2:], [(2, 16, 24), (2, 16, 24), (2, 24, 16)])]
    a = moe.moe_share_forward(x, gw, *ws, 2, 2)
    b = moe.moe_share_forward(x, gw, *ws, 2, 2, True, moe.route_softmax)
    assert (np.asarray(a[0]) == np.asarray(b[0])).all()
    assert (np.asarray(a[1]) == np.asarray(b[1])).all()


def test_the_bias_changes_the_selection_and_not_the_weights(logits):
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(6), (32,))
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    plain_i, plain = moe.route_sigmoid(logits, 4)
    top_i, gates = moe.route_sigmoid(logits, 4, True, bias, 1.0)
    # chosen by s + bias ...
    want_i = np.argsort(-(s + np.asarray(bias, np.float64)), -1,
                        kind="stable")[:, :4]
    assert (np.sort(np.asarray(top_i), -1) == np.sort(want_i, -1)).all()
    changed = (np.sort(np.asarray(top_i), -1)
               != np.sort(np.asarray(plain_i), -1)).any(-1)
    assert 0.2 < changed.mean() < 1.0           # the bias bites
    # ... weighed by s alone, over the chosen ones' sum + 1e-6
    chosen = np.take_along_axis(s, np.asarray(top_i), -1)
    assert np.allclose(np.asarray(gates),
                       chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                       rtol=1e-6)
    # a token whose selection the bias left alone keeps its weights
    same = ~changed
    assert same.any() and np.allclose(
        np.sort(np.asarray(gates)[same], -1),
        np.sort(np.asarray(plain)[same], -1), rtol=1e-6)
    # no normalisation, a scaling factor; and the bias takes no gradient
    _, raw = moe.route_sigmoid(logits, 4, False, bias, 2.5)
    assert np.allclose(np.asarray(raw), 2.5 * chosen, rtol=1e-6)
    d_bias = jax.grad(lambda b: jnp.sum(
        moe.route_sigmoid(logits, 4, True, b)[1] ** 2))(bias)
    assert float(jnp.abs(d_bias).max()) == 0.0


def _ref_cfg(**model):
    from benchmark import manifest
    cfg = copy.deepcopy(manifest.load_json(
        ROOT, "benchmark/configs/lfm2_8b_a1b_ep4_l5_train.json"))
    cfg["model"].update(
        hidden_size=64, intermediate_size=96, moe_intermediate_size=48,
        num_attention_heads=4, num_key_value_heads=2, num_experts=2,
        expert_share=[1, 4], num_experts_per_tok=2, vocab_size=128,
        torch_dtype="float32")
    cfg["model"].update(model)
    cfg["init_scale"] = 0.3
    return cfg


def test_the_four_shares_add_up_to_the_uncut_references_whole_layer():
    from benchmark.reference import lfm2_moe_ref as ref
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    d, h, e, k = 16, 24, 8, 2
    x = jax.random.normal(ks[0], (2, 32, d))
    lw = {"wr": jax.random.normal(ks[1], (d, e)),
          "eg": 0.3 * jax.random.normal(ks[2], (e, d, h)),
          "eu": 0.3 * jax.random.normal(ks[3], (e, d, h)),
          "ed": 0.3 * jax.random.normal(ks[4], (e, h, d)),
          "eb": 0.5 * jax.random.normal(ks[5], (e,))}
    whole_model = dict(_ref_cfg()["model"], num_experts=e,
                       expert_share=[0, 1], num_experts_per_tok=k)
    whole = jnp.stack([ref.held_experts(row, lw, whole_model) for row in x])
    route = functools.partial(moe.route_sigmoid, expert_bias=lw["eb"])
    total, rows = 0, []
    for share in range(4):
        lo, n = share * 2, 2
        out, r, _ = moe.moe_share_forward(
            x, lw["wr"], lw["eg"][lo:lo + n], lw["eu"][lo:lo + n],
            lw["ed"][lo:lo + n], k, lo, True, route)
        # each share is the reference told the same share
        part = dict(whole_model, num_experts=n, expert_share=[share, 4])
        cut = {key: (v[lo:lo + n] if key in ("eg", "eu", "ed") else v)
               for key, v in lw.items()}
        assert close(out, jnp.stack([ref.held_experts(row, cut, part)
                                     for row in x]))
        total, rows = total + out, rows + list(np.asarray(r))
    assert close(total, whole)
    assert sum(rows) == 2 * 32 * k              # every (token, choice) once
    # without the bias the selection, and so the layer, is another
    plain = sum(moe.moe_share_forward(
        x, lw["wr"], lw["eg"][i:i + 2], lw["eu"][i:i + 2],
        lw["ed"][i:i + 2], k, i, True, moe.route_sigmoid)[0]
        for i in (0, 2, 4, 6))
    assert not close(plain, whole, 1e-3)


def test_the_layer_takes_the_rule_it_is_told():
    paddle.seed(3)
    layer = nn.MoEShareLayer(16, 24, 8, 2, share=(1, 4),
                             score_func="sigmoid", expert_bias=True)
    assert [n for n, _ in layer.named_buffers()] == ["rows", "expert_bias"]
    assert layer.expert_bias.shape == [8]
    assert str(layer.expert_bias.dtype).endswith("float32")
    assert len(layer.parameters()) == 4         # the bias is no parameter
    reg = telemetry.default_tracer().metrics
    before = reg.value("moe.route.sigmoid_bias") or 0
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 32, 16)).astype(np.float32))
    plain = layer(x)._value
    assert reg.value("moe.route.sigmoid_bias") == before + 1
    layer.expert_bias._replace(jnp.asarray(
        [0.0, 0.0, 5.0, 5.0, 0.0, 0.0, 0.0, 0.0], jnp.float32))
    favoured = layer(x)._value                  # everyone picks the held two
    assert layer.routing_counts()["rows_held"] > 2 * 32 * 2
    assert not close(favoured, plain, 1e-3)
    with pytest.raises(ValueError, match="no selection bias"):
        nn.MoEShareLayer(16, 24, 8, 2, expert_bias=True)
    with pytest.raises(ValueError, match="softmax, sigmoid"):
        nn.MoEShareLayer(16, 24, 8, 2, score_func="tanh")


# -- the model ----------------------------------------------------------------

def test_recompute_trains_the_same_and_every_kind_of_layer_counts():
    ids = np.random.default_rng(0).integers(0, 128, (2, 64), dtype=np.int32)
    reg = telemetry.default_tracer().metrics
    losses = {}
    for rc in (False, True):
        paddle.seed(0)
        cfg = lfm2_moe_tiny(use_recompute=rc)
        model = Lfm2MoeForCausalLM(cfg)
        kinds = {(layer.is_attention, layer.is_dense)
                 for layer in model.model.layers}
        assert len(kinds) == 4                  # two choices, four kinds
        assert model.lm_head is None            # tied
        convs = reg.value("short_conv.layers") or 0
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
        t = paddle.to_tensor(ids)
        losses[rc] = [float(step(t, t)._value) for _ in range(3)]
        assert losses[rc][2] < losses[rc][0]
        counts = model.routing_counts()
        # steps x expert layers x tokens x top-2
        assert counts["rows_routed"] == 3 * 2 * 2 * 64 * 2
        assert 0 < counts["rows_max_expert"] <= counts["rows_held"] \
            < counts["rows_routed"]
        assert reg.value("short_conv.layers") >= convs + 2
    assert np.allclose(losses[True], losses[False], rtol=1e-5)
    assert reg.snapshot()["counters"]["moe.rows_routed"] \
        == counts["rows_routed"] == reg.value("moe.rows_routed")
    assert reg.value("moe.rows_walked") == counts["rows_walked"] \
        >= counts["rows_held"]
    assert reg.value("attn.flash.head_dim") == 16
    assert reg.value("moe.route.sigmoid_bias") >= 2


def test_the_layers_named_scopes_reach_the_compiled_step():
    paddle.seed(0)
    model = Lfm2MoeForCausalLM(lfm2_moe_tiny())
    ids = jnp.zeros((2, 64), jnp.int32)
    params = [p._value for p in model.parameters()]
    buffers = [b._value for _, b in model.named_buffers()]
    from paddle_tpu.jit import _wrap_tree, functional_call

    def loss(params):
        out, _ = functional_call(model, params, buffers, (ids,))
        return model.loss(_wrap_tree(out), paddle.to_tensor(ids))._value
    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    for scope in ("embed", "layer0/short_conv", "layer0/mlp", "layer1/attn",
                  "layer1/mlp", "layer2/short_conv", "layer2/moe",
                  "layer3/attn", "layer3/moe", "final_norm", "lm_head",
                  "loss"):
        assert scope in text, scope
    assert "layer0/moe" not in text and "layer1/short_conv" not in text


def test_the_config_refuses_what_the_decoder_has_not():
    with pytest.raises(ValueError, match="layer_types for"):
        Lfm2MoeConfig(num_hidden_layers=3)
    with pytest.raises(ValueError, match="there are: conv, full_attention"):
        lfm2_moe_tiny(layer_types=("conv", "sliding", "conv", "conv"))


@pytest.fixture(scope="module")
def against_reference():
    """The program's model with the benchmark's seeded leaves (gains moved
    off one) and a selection bias that is not zero, through one
    ``jit.TrainStep``: its loss, every leaf's gradient as AdamW got it,
    its logits; and the plain reference's."""
    from benchmark import weights as W
    from benchmark.families import lm_lfm2_moe as fam
    from benchmark.reference import lfm2_moe_ref as ref
    cfg = _ref_cfg()
    model, names = fam.build_trainable(cfg)
    named = dict(model.named_parameters())
    seeded = W.Leaves(fam, cfg, 5)
    for name, shape in seeded.shapes.items():
        leaf = seeded.make(name)
        if len(shape) == 1:
            leaf = leaf + 0.1 * jax.random.normal(
                jax.random.PRNGKey(W.leaf_tag(name)), shape)
        named[names[name]]._replace(leaf)
    bias = {}
    for i, layer in enumerate(model.model.layers):
        if not layer.is_dense:
            b = 0.2 * jax.random.normal(jax.random.PRNGKey(100 + i), (8,))
            layer.feed_forward.expert_bias._replace(b)
            bias[f"layers.{i}.eb"] = b
    # copies: the step donates the parameters it is given
    ref_params = {n: jnp.copy(named[names[n]]._value) for n in seeded.shapes}
    ref_params.update(bias)
    ids = np.random.default_rng(0).integers(0, 128, (2, 64), dtype=np.int32)
    logits = model(paddle.to_tensor(ids))._value
    opt = optimizer.AdamW(learning_rate=1e-6, beta1=0.9,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
    t = paddle.to_tensor(ids)
    value = float(step(t, t)._value)
    index = {id(p): i for i, p in enumerate(opt._parameter_list)}
    grads = {n: opt._state["m"][index[id(named[names[n]])]] / (1.0 - 0.9)
             for n in seeded.shapes}
    return cfg, ids, ref_params, value, grads, logits, ref


def test_logits_loss_and_every_leafs_gradient_against_the_plain_reference(
        against_reference):
    """Both sides are float32 and differ in the order of their sums alone:
    1e-4 of a leaf's largest gradient is a hundred roundings."""
    cfg, ids, ref_params, value, grads, logits, ref = against_reference
    ref_value, ref_grads = ref.loss_and_grads(ref_params, ids, cfg)
    assert value == pytest.approx(ref_value, rel=1e-5)
    assert set(grads) == set(ref_grads) and len(grads) == 49
    for name, want in ref_grads.items():
        assert float(jnp.abs(want).max()) > 0, name
        assert close(grads[name], want, 1e-4), name
    for row in range(2):
        want = ref.sequence_logits_of(ref_params, jnp.asarray(ids[row]),
                                      cfg["model"])
        assert close(logits[row], want, 1e-5)
    # the bias was part of it: without it the reference's loss is another
    no_bias = {k: v for k, v in ref_params.items() if not k.endswith(".eb")}
    assert abs(ref.loss_and_grads(no_bias, ids, cfg)[0] - ref_value) \
        > 1e-4 * ref_value


def test_the_lower_precision_control_fails_that_tolerance(against_reference):
    cfg, ids, ref_params, _, grads, _, ref = against_reference
    low_params = {k: (ref.stored_fp8(v) if v.ndim >= 2 and k != "embed"
                      else v) for k, v in ref_params.items()}
    _, low = ref.loss_and_grads(low_params, ids, cfg, precision="lower")
    assert any(not close(low[name], grads[name], 1e-4) for name in grads)
