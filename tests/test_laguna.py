"""Laguna-S-2.1's decoder on the CPU at a small size: the partial YaRN
rotary embedding against a transcription of its formula, the per-head
gate against a loop over the heads, the softmax rule's scaling, the 32
shares of an expert layer with its shared expert against the uncut
layer, the flash kernels (interpret mode) under a window of one block,
and the whole model through ``jit.TrainStep`` against the benchmark's
plain reference (``benchmark/reference/laguna_ref.py``)."""
import copy
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import optimizer
from paddle_tpu.models import (LagunaConfig, LagunaForCausalLM,
                               laguna_tiny)
from paddle_tpu.ops import moe
from paddle_tpu.ops.flash_attention import _sdpa_core
from paddle_tpu.ops.pallas import flash_attention as pf
from paddle_tpu.ops.rope import (build_rope_cache, rope_reference,
                                 yarn_inv_freq)
from paddle_tpu.utils import telemetry

from test_smallthinker import _qkv, _tiles_in_band

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def close(a, b, tol=1e-5):
    return float(jnp.max(jnp.abs(a - b))) <= tol * (
        1.0 + float(jnp.max(jnp.abs(b))))


# -- the rotary embeddings ----------------------------------------------------

def _yarn_by_hand(dim, base, factor, original, fast, slow):
    """The formula written out pair by pair."""
    corr = lambda r: dim * math.log(original / (r * 2 * math.pi)) \
        / (2 * math.log(base))
    low, high = max(math.floor(corr(fast)), 0), \
        min(math.ceil(corr(slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        own = base ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(own * (1 - ramp) + own / factor * ramp)
    return np.asarray(out)


def test_yarn_frequencies_against_the_formula():
    """Laguna's full layers: 64 rotated dimensions at theta 500,000,
    factor 128 over 8,192 positions: pairs 0-8 keep their frequency,
    18-31 take it over 128, 9-17 a blend."""
    got = yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    want = _yarn_by_hand(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    own = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:9], own[:9], rtol=1e-6)
    np.testing.assert_allclose(got[18:], own[18:] / 128, rtol=1e-6)
    assert ((got[9:18] < own[9:18]) & (got[9:18] > own[9:18] / 128)).all()


def test_partial_rotary_turns_the_first_dimensions_and_passes_the_rest():
    """cos and sin of 8 of 16 dimensions, scaled 1.3: the first 8 of
    each head rotate as ``x cos + rotate_half(x) sin`` inside them, by a
    numpy transcription; the other 8 come out as they went in, not
    scaled."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1, 12, 3, 16)).astype(np.float32)
    inv = yarn_inv_freq(8, 500.0, 4.0, 8, 32.0, 1.0)
    cos, sin = build_rope_cache(12, 8, 500.0, jnp.float32, inv, 1.3)
    got = np.asarray(rope_reference(jnp.asarray(x), cos, sin))
    ang = np.arange(12)[:, None] * inv[None, :]
    for t in range(12):
        for h in range(3):
            a, b = x[0, t, h, :4], x[0, t, h, 4:8]
            c, s = 1.3 * np.cos(ang[t]), 1.3 * np.sin(ang[t])
            np.testing.assert_allclose(got[0, t, h, :4], a * c - b * s,
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(got[0, t, h, 4:8], b * c + a * s,
                                       rtol=1e-5, atol=1e-6)
    assert (got[..., 8:] == x[..., 8:]).all()
    # a cache as wide as the head is today's program, scale 1 no multiply
    full = build_rope_cache(12, 16, 500.0)
    assert str(jax.make_jaxpr(lambda: build_rope_cache(12, 16, 500.0))()) \
        == str(jax.make_jaxpr(lambda: build_rope_cache(
            12, 16, 500.0, jnp.float32, None, 1.0))())
    np.testing.assert_array_equal(
        rope_reference(jnp.asarray(x), *full),
        jnp.asarray(x) * full[0] + jnp.concatenate(
            [-x[..., 8:], x[..., :8]], -1) * full[1])


def test_each_layer_takes_its_rotary_embedding_from_its_kind():
    model = LagunaForCausalLM(laguna_tiny())
    got = [(a.rotary_dim, a.yarn, a.rope_scale, a.window, a.num_heads)
           for a in (layer.self_attn for layer in model.model.layers)]
    assert got == [(8, True, 1.2, None, 4)] + [(16, False, 1.0, 16, 6)] * 3 \
        + [(8, True, 1.2, None, 4)]
    published = LagunaConfig()
    dim, inv, scale = published.rotary(0)
    assert (dim, scale) == (64, 1.4852030263919618)
    np.testing.assert_allclose(
        inv, _yarn_by_hand(64, 500000.0, 128.0, 8192, 32.0, 1.0),
        rtol=1e-6)
    assert published.rotary(1) == (128, None, 1.0)   # the plain one
    assert published.window(1) == 512 and published.window(4) is None


def test_the_reference_transcribes_the_same_frequencies():
    from benchmark.reference import laguna_ref as ref
    np.testing.assert_allclose(
        ref.yarn(64, 500000.0, 128.0, 8192.0, 32.0, 1.0),
        yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0), rtol=1e-6)


# -- the per-head gate --------------------------------------------------------

def test_the_gate_against_a_loop_over_the_heads():
    """One window layer of the tiny model in float32: its output is the
    sum over heads h of (sigmoid(x W_g)[h] * attention head h) through
    that head's rows of W_o."""
    paddle.seed(1)
    cfg = laguna_tiny()
    attn = LagunaForCausalLM(cfg).model.layers[1].self_attn
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.float32)
    got = attn(paddle.to_tensor(x))._value
    wq, wk, wv, wo, wg = (p._value for p in (
        attn.q_proj.weight, attn.k_proj.weight, attn.v_proj.weight,
        attn.o_proj.weight, attn.g_proj.weight))
    q = attn._rope((x @ wq).reshape(2, 32, 6, 16))
    k = attn._rope((x @ wk).reshape(2, 32, 2, 16))
    v = (x @ wv).reshape(2, 32, 2, 16)
    o = _sdpa_core(q, k, v, None, True, 16 ** -0.5, window=16)
    gate = jax.nn.sigmoid(x @ wg)
    want = sum(gate[..., h:h + 1] * o[:, :, h] @ wo[16 * h:16 * (h + 1)]
               for h in range(6))
    assert close(got, want)
    ungated = o.reshape(2, 32, 96) @ wo
    assert not close(got, ungated, 1e-2)


# -- routing and the shares ---------------------------------------------------

def test_route_softmax_at_its_default_scaling_is_the_old_rule_to_the_bit():
    """Keye's and SmallThinker's rule: the default traces no multiply, so
    their steps are the program they were; Laguna's 2.5 multiplies the
    renormalised weights."""
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(4), (256, 64))

    def old(logits, k, norm):
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, k)
        gates = top_p / jnp.sum(top_p, -1, keepdims=True) if norm else top_p
        return top_i, gates

    for norm in (True, False):
        text = lambda f: str(jax.make_jaxpr(lambda l: f(l, 6, norm))(logits))
        assert text(moe.route_softmax) == text(old) == text(
            lambda l, k, n: moe.route_softmax(l, k, n, 1.0))
        for a, b in zip(moe.route_softmax(logits, 6, norm),
                        old(logits, 6, norm)):
            assert (np.asarray(a) == np.asarray(b)).all()
    top_i, gates = moe.route_softmax(logits, 10, True, 2.5)
    assert (np.asarray(top_i) == np.asarray(old(logits, 10, True)[0])).all()
    np.testing.assert_allclose(gates, 2.5 * old(logits, 10, True)[1],
                               rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(gates, -1), 2.5, rtol=1e-5)


def _ref_model(**model):
    from benchmark import manifest
    cfg = copy.deepcopy(manifest.load_json(
        ROOT, "benchmark/configs/laguna_s21_ep32_l5_train.json"))
    cfg["model"].update(
        hidden_size=32, head_dim=16, num_key_value_heads=1,
        num_attention_heads_per_layer=[6, 9, 9, 9, 6],
        intermediate_size=48, moe_intermediate_size=24,
        shared_expert_intermediate_size=20, num_experts=2,
        expert_share=[1, 32], num_experts_per_tok=10, vocab_size=96,
        sliding_window=16, torch_dtype="float32")
    cfg["model"].update(model)
    cfg["init_scale"] = 0.3
    return cfg


def test_the_32_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """64 experts over 32 shares of 2, top-10 scaled 2.5: what each share
    computes (``moe_share_forward`` told its share) summed over the 32,
    with the shared expert, which every chip computes alike, counted
    once, is the reference's whole feed-forward of the uncut layer."""
    from benchmark.reference import laguna_ref as ref
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    d, w, sw, e = 32, 24, 20, 64
    x = jax.random.normal(ks[0], (2, 40, d))
    lw = {"wr": jax.random.normal(ks[1], (d, e)),
          "eg": 0.3 * jax.random.normal(ks[2], (e, d, w)),
          "eu": 0.3 * jax.random.normal(ks[3], (e, d, w)),
          "ed": 0.3 * jax.random.normal(ks[4], (e, w, d)),
          "sg": 0.3 * jax.random.normal(ks[5], (d, sw)),
          "su": 0.3 * jax.random.normal(ks[6], (d, sw)),
          "sd": 0.3 * jax.random.normal(ks[7], (sw, d))}
    whole_model = dict(_ref_model()["model"], num_experts=e,
                       expert_share=[0, 1])
    route = lambda l, k, n: moe.route_softmax(l, k, n, 2.5)
    # one program for the 32 shares: the share's first expert is an operand
    share = jax.jit(lambda lo, eg, eu, ed: moe.moe_share_forward(
        x, lw["wr"], eg, eu, ed, 10, lo, True, route)[:2])
    with jax.default_matmul_precision("highest"):
        whole = jnp.stack([ref.feed_forward(u, lw, whole_model, False)
                           for u in x])
        total, rows = 0, []
        for lo in range(0, 64, 2):
            out, seen = share(lo, *(lw[k][lo:lo + 2]
                                    for k in ("eg", "eu", "ed")))
            total, rows = total + out, rows + list(np.asarray(seen))
        shared = (jax.nn.silu(x @ lw["sg"]) * (x @ lw["su"])) @ lw["sd"]
    assert close(total + shared, whole, 1e-4)
    assert sum(rows) == 2 * 40 * 10             # every (token, choice) once
    # the shared expert counted on every share would not be the layer
    assert not close(total + 32 * shared, whole, 1e-2)


# -- the flash kernels under a window of one block ----------------------------

@pytest.mark.parametrize("h,hk", [(6, 1), (9, 1), (18, 2)])
def test_a_window_of_one_block_against_the_dense_reference(h, hk):
    """Laguna's window layers at the kernels' block: the window is one
    block of keys (512 on the chip, 32 here), so a query block walks two
    key blocks and the band cuts both; groups of 6 and 9 query heads a
    key head. Interpret mode, float32 on both sides: 2e-5 absolute."""
    q, k, v, do = _qkv(256, h, hk)
    kernel = lambda q, k, v: pf.flash_attention_pallas(
        q, k, v, True, None, 32, 32, 32)
    dense = lambda q, k, v: _sdpa_core(q, k, v, None, True, 8 ** -0.5,
                                       window=32)
    np.testing.assert_allclose(kernel(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * do), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_no_tile_outside_a_one_block_band_is_computed():
    """The NaN-poison check at window == block: query tile i sees key
    tiles i - 1 and i alone. With every other key tile NaN (k and v) the
    tile's output and dq stay finite only if the forward and dq loops
    never touch one; with q and the cotangent NaN in every query tile
    but j and j + 1, key tile j's dk and dv likewise."""
    block, seq, h, hk = 32, 256, 9, 1
    q, k, v, do = _qkv(seq, h, hk, b=1)
    n = seq // block

    @jax.jit
    def run(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: pf.flash_attention_pallas(
            *a, True, None, block, block, block), q, k, v)
        return (out,) + vjp(do)

    def poisoned(x, keep):
        tiles = jnp.repeat(jnp.asarray([j in keep for j in range(n)]),
                           block)
        return jnp.where(tiles[None, :, None, None], x, jnp.nan)

    rows = lambda x, i: np.asarray(x[:, i * block:(i + 1) * block])
    for i in range(n):
        keys = _tiles_in_band(i, n, block, block, of_keys=True)
        assert keys == [j for j in (i - 1, i) if j >= 0]
        out, dq, _, _ = run(q, poisoned(k, keys), poisoned(v, keys), do)
        assert np.isfinite(rows(out, i)).all(), ("fwd", i)
        assert np.isfinite(rows(dq, i)).all(), ("dq", i)
        queries = _tiles_in_band(i, n, block, block, of_keys=False)
        assert queries == [j for j in (i, i + 1) if j < n]
        _, _, dk, dv = run(poisoned(q, queries), k, v,
                           poisoned(do, queries))
        assert np.isfinite(rows(dk, i)).all(), ("dk", i)
        assert np.isfinite(rows(dv, i)).all(), ("dv", i)


def test_the_tiles_at_the_cells_shape_by_hand():
    """1 x 8,192 under 512 keys at tiles of 512: query tile i walks key
    tiles i - 1 and i, 31 of the 136 causal tiles, and every one of them
    is cut (the diagonal cuts 16, the band's lower edge 15); each grid
    row of sixteen blocks meets a tile it walks."""
    dq = pf._bwd_steps(pf._dq_spans(16, 16, 512, 512, 0, True, 512), 16)
    dkv = pf._bwd_steps(pf._dkv_spans(16, 16, 512, 512, 0, True, 512), 16)
    for table, rows_are_queries in ((dq, True), (dkv, False)):
        walked, masked = set(), set()
        for row, _, lo, a, b, hi, _, _ in table.reshape(-1, pf._FIELDS):
            walked |= {(row, j) for j in range(lo, hi)}
            masked |= {(row, j) for j in list(range(lo, a))
                       + list(range(b, hi))}
        pairs = walked if rows_are_queries else {(j, i) for i, j in walked}
        assert pairs == {(i, j) for i in range(16) for j in (i - 1, i)
                         if j >= 0}
        assert len(walked) == 31 and masked == walked
        fields = table.reshape(-1, pf._FIELDS)
        assert (fields[:, pf._HI] > fields[:, pf._LO]).all()


# -- the model ----------------------------------------------------------------

def test_the_config_refuses_what_the_decoder_has_not():
    with pytest.raises(ValueError, match="layer_types has to name"):
        laguna_tiny(layer_types=("full_attention",) * 4)
    with pytest.raises(ValueError, match="mlp_layer_types has to name"):
        laguna_tiny(mlp_layer_types=("dense", "moe", "sparse", "sparse",
                                     "sparse"))
    with pytest.raises(ValueError, match="5 query heads over 2"):
        laguna_tiny(num_attention_heads_per_layer=(4, 6, 5, 6, 4))
    rope = copy.deepcopy(laguna_tiny().rope_parameters)
    rope["full_attention"]["rope_type"] = "longrope"
    with pytest.raises(ValueError, match="default, yarn"):
        laguna_tiny(rope_parameters=rope)


def test_one_trace_of_the_step_counts_each_mechanism():
    """Five gated attention layers, two of them under YaRN over 8 of 16
    dimensions, four expert layers with a shared expert, three window
    layers under 16 keys; the routing rule's scaling reaches the
    counters' totals as rows routed."""
    reg = telemetry.default_tracer().metrics
    names = ("attn.gate.per_head", "rope.yarn", "moe.shared_expert",
             "attn.flash.window")
    paddle.seed(0)
    model = LagunaForCausalLM(laguna_tiny())
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
    ids = paddle.to_tensor(np.random.default_rng(0).integers(
        0, 128, (2, 64), dtype=np.int32))
    before = {n: reg.value(n) or 0 for n in names}
    losses = [float(step(ids, ids)._value) for _ in range(3)]
    assert losses[2] < losses[0]
    took = {n: reg.value(n) - before[n] for n in names}
    assert took == {"attn.gate.per_head": 5, "rope.yarn": 2,
                    "moe.shared_expert": 4, "attn.flash.window": 3}
    assert reg.value("rope.rotary_dim") == 8
    assert reg.value("attn.flash.window_size") == 16
    counts = model.routing_counts()
    assert counts["rows_routed"] == 3 * 4 * 2 * 64 * 3   # steps x layers
    assert 0 < counts["rows_held"] < counts["rows_routed"]
    assert reg.snapshot()["counters"]["moe.rows_routed"] \
        == counts["rows_routed"]


def test_the_layers_named_scopes_reach_the_compiled_step():
    paddle.seed(0)
    model = LagunaForCausalLM(laguna_tiny(use_recompute=True))
    ids = jnp.zeros((1, 64), jnp.int32)
    params = [p._value for p in model.parameters()]
    buffers = [b._value for _, b in model.named_buffers()]
    from paddle_tpu.jit import _wrap_tree, functional_call

    def loss(params):
        out, _ = functional_call(model, params, buffers, (ids,))
        return model.loss(_wrap_tree(out), paddle.to_tensor(ids))._value
    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    for scope in ("embed", "layer0/attn_global", "layer0/mlp",
                  "layer1/attn_window", "layer3/attn_window",
                  "layer4/attn_global", "layer1/moe", "layer4/moe",
                  "layer2/moe/router", "layer2/moe/shared", "final_norm",
                  "lm_head", "loss"):
        assert scope in text, scope
    for absent in ("layer0/moe", "layer1/mlp", "layer0/attn_window",
                   "layer4/attn_window", "layer1/attn_global"):
        assert absent not in text, absent


def _program_against_reference(cfg, seed=5, steps=1, lr=1e-6):
    """The program's model with the benchmark's seeded leaves (gains moved
    off one) through ``jit.TrainStep``: its logits, each step's loss,
    every leaf's first gradient as AdamW got it and every leaf after the
    steps; the reference's leaves as they started."""
    from benchmark import weights as W
    from benchmark.families import lm_laguna as fam
    model, names = fam.build_trainable(cfg)
    named = dict(model.named_parameters())
    seeded = W.Leaves(fam, cfg, seed)
    for name, shape in seeded.shapes.items():
        leaf = seeded.make(name)
        if len(shape) == 1:
            leaf = leaf + 0.1 * jax.random.normal(
                jax.random.PRNGKey(W.leaf_tag(name)), shape)
        named[names[name]]._replace(leaf)
    # copies: the step donates the parameters it is given
    ref_params = {n: jnp.copy(named[names[n]]._value) for n in seeded.shapes}
    ids = np.random.default_rng(0).integers(
        0, cfg["model"]["vocab_size"], (2, 64), dtype=np.int32)
    logits = model(paddle.to_tensor(ids))._value
    opt = optimizer.AdamW(learning_rate=lr, beta1=0.9, beta2=0.999,
                          epsilon=1e-8, weight_decay=0.01,
                          parameters=model.parameters())
    step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
    t = paddle.to_tensor(ids)
    losses, grads = [], None
    index = {id(p): i for i, p in enumerate(opt._parameter_list)}
    for _ in range(steps):
        losses.append(float(step(t, t)._value))
        if grads is None:
            grads = {n: opt._state["m"][index[id(named[names[n]])]]
                     / (1.0 - 0.9) for n in seeded.shapes}
    after = {n: named[names[n]]._value for n in seeded.shapes}
    return ids, ref_params, logits, losses, grads, after


@pytest.fixture(scope="module")
def against_reference():
    from benchmark.reference import laguna_ref as ref
    cfg = _ref_model()
    return (cfg, ref) + _program_against_reference(cfg, steps=3, lr=1e-3)


def test_logits_loss_and_every_leafs_gradient_against_the_plain_reference(
        against_reference):
    """Both sides are float32 and differ in the order of their sums alone:
    1e-4 of a leaf's largest gradient is a hundred roundings. Groups of 6
    and 9 query heads over one key head, the sequence (64) four windows
    (16) long, 2 of 64 experts held, top-10 scaled 2.5."""
    cfg, ref, ids, ref_params, logits, losses, grads, _ = against_reference
    ref_value, ref_grads = ref.loss_and_grads(ref_params, ids, cfg)
    assert losses[0] == pytest.approx(ref_value, rel=1e-5)
    assert set(grads) == set(ref_grads) and len(grads) == 69
    for name, want in ref_grads.items():
        assert float(jnp.abs(want).max()) > 0, name
        assert close(grads[name], want, 1e-4), name
    for row in range(2):
        want = ref.sequence_logits_of(ref_params, jnp.asarray(ids[row]),
                                      cfg["model"])
        assert close(logits[row], want, 1e-5)
    # each mechanism was part of it: the reference without the window,
    # with the plain embedding on the full layers, or without the
    # routing's scale reads another loss
    rope = copy.deepcopy(cfg["model"]["rope_parameters"])
    rope["full_attention"] = dict(rope["sliding_attention"])
    row_loss = lambda model: float(jax.jit(lambda p, i: ref.row_loss(
        p, i, model))(ref_params, jnp.asarray(ids[0])))
    with jax.default_matmul_precision("highest"):
        base = row_loss(cfg["model"])
        for other in ({"sliding_window": 64}, {"rope_parameters": rope},
                      {"moe_routed_scaling_factor": 1.0}):
            changed = row_loss(dict(cfg["model"], **other))
            assert abs(changed - base) > 1e-5 * base, other


def test_three_adamw_steps_against_the_plain_reference(against_reference):
    """Three steps at lr 1e-3, so that the change is no rounding: each
    step's loss to 1e-5 and every leaf after the third to 2e-3 of its
    largest entry (Adam divides by sqrt(v): a gradient entry near zero
    turns a rounding into a step of another sign)."""
    from benchmark.reference import adamw
    cfg, ref, ids, ref_params, _, losses, _, after = against_reference
    params = {k: jnp.copy(v) for k, v in ref_params.items()}
    state = {"m": {}, "v": {}}
    opt = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
           "epsilon": 1e-8, "weight_decay": 0.01}
    for t in range(1, 4):
        value, grads = ref.loss_and_grads(params, ids, cfg)
        assert losses[t - 1] == pytest.approx(value, rel=1e-5), t
        params, state = adamw.adamw_step(params, grads, state, opt, t)
    for name, want in params.items():
        assert float(jnp.abs(want - ref_params[name]).max()) > 1e-4, name
        assert close(after[name], want, 2e-3), name


def test_the_lower_precision_control_fails_that_tolerance(against_reference):
    cfg, ref, ids, ref_params, _, _, grads, _ = against_reference
    low_params = {k: (ref.stored_fp8(v) if v.ndim >= 2 and k != "embed"
                      else v) for k, v in ref_params.items()}
    _, low = ref.loss_and_grads(low_params, ids, cfg, precision="lower")
    assert any(not close(low[name], grads[name], 1e-4) for name in grads)
