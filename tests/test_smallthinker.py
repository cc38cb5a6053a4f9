"""SmallThinker-21BA3B's decoder on the CPU at a small size: the flash
kernels under a window (interpret mode) against the dense reference, the
expert layer with a router input of its own and a ReLU gate, the four
shares against the uncut layer, and the whole model through
``jit.TrainStep`` against the benchmark's plain reference
(``benchmark/reference/smallthinker_ref.py``)."""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.models import (SmallThinkerConfig, SmallThinkerForCausalLM,
                               smallthinker_tiny)
from paddle_tpu.ops import moe
from paddle_tpu.ops.flash_attention import (_sdpa_core, flash_attention,
                                            flash_attention_reference)
from paddle_tpu.ops.pallas import flash_attention as pf
from paddle_tpu.utils import telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def close(a, b, tol=1e-5):
    return float(jnp.max(jnp.abs(a - b))) <= tol * (
        1.0 + float(jnp.max(jnp.abs(b))))


# -- the flash kernels under a window -----------------------------------------

def _qkv(seq, h, hk, d=8, b=2, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda n: jnp.asarray(rng.randn(b, seq, n, d) * 0.5, jnp.float32)
    return mk(h), mk(hk), mk(hk), jnp.asarray(rng.randn(b, seq, h, d),
                                              jnp.float32)


# (seq, window, block, q heads, kv heads, head size); the backward pass
# streams sixteen blocks a step, so a sequence of more than sixteen
# blocks is walked in several chunks
WINDOW_CASES = [
    (128, 40, 32, 4, 2, 8),         # smaller than the sequence, no
                                    # multiple of the block
    (128, 32, 32, 4, 2, 8),         # one block
    (128, 128, 32, 4, 2, 8),        # the sequence itself
    (128, 200, 32, 4, 2, 8),        # larger than the sequence
    (128, 1, 32, 4, 2, 8),          # a query sees itself alone
    (128, 40, 32, 7, 1, 8),         # SmallThinker's group of seven
    (256, 40, 8, 7, 1, 8),          # 32 blocks: two chunks
    (256, 100, 32, 4, 2, 8),        # wider than a block
    (256, 64, 32, 4, 2, 8),         # two blocks
    (256, 77, 64, 4, 4, 8),         # blocks of 64: one chunk
    (1024, 200, 16, 7, 1, 8),       # 64 blocks, 4 chunks: the band
                                    # wider than a block, far narrower
                                    # than the sequence
    (512, 100, 16, 4, 2, 64),       # two chunks at a head of 64
]


@pytest.mark.parametrize("seq,window,block,h,hk,d", WINDOW_CASES)
def test_windowed_kernels_against_the_dense_reference(seq, window, block,
                                                     h, hk, d):
    """Interpret mode, float32 on both sides: the orders of the sums
    differ and nothing else, 2e-5 absolute is twenty roundings of values
    near 1."""
    q, k, v, do = _qkv(seq, h, hk, d)
    kernel = lambda q, k, v: pf.flash_attention_pallas(
        q, k, v, True, None, block, block, window)
    dense = lambda q, k, v: _sdpa_core(q, k, v, None, True, d ** -0.5,
                                       window=window)
    np.testing.assert_allclose(kernel(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * do), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_segments_over_many_chunks_against_the_dense_reference(causal):
    """Packed sequences over 32 blocks of 16 (two chunks a side), group
    seven: the segment compare in every tile the streamed kernels walk."""
    from paddle_tpu.ops.flash_attention import _sdpa_segmented_core
    q, k, v, do = _qkv(512, 7, 1, b=1)
    seg = jnp.asarray(np.repeat(np.arange(5), [70, 130, 44, 200, 68])[None],
                      jnp.int32)
    kernel = lambda q, k, v: pf.flash_attention_pallas_segmented(
        q, k, v, seg, seg, causal, None, 16, 16)
    dense = lambda q, k, v: _sdpa_segmented_core(q, k, v, seg, seg, causal,
                                                 8 ** -0.5)
    np.testing.assert_allclose(kernel(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * do), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * do), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def _tiles_in_band(i, n, block, window, of_keys):
    """The tiles of ``block`` keys that hold a key some query of query
    tile ``i`` sees under ``window`` or, ``of_keys`` false, the query
    tiles that hold a query which sees some key of key tile ``i``."""
    lo, hi = i * block, i * block + block - 1
    if of_keys:     # keys s with s <= t and s > t - window, t in lo..hi
        return [j for j in range(n)
                if j * block <= hi and j * block + block - 1 > lo - window]
    return [j for j in range(n)
            if j * block + block - 1 >= lo and j * block < hi + window]


@pytest.mark.parametrize("seq,window,block,h,hk,d", [
    c for c in WINDOW_CASES if c[1] in (40, 64, 77, 100)])
def test_no_tile_outside_the_band_is_computed(seq, window, block, h, hk, d):
    """What the kernels' loops visit, observed and not reckoned: with
    every key tile outside a query tile's band set to NaN (k and v), that
    tile's output and dq stay finite only if the forward and dq loops
    never touch such a tile (a masked score would still carry 0 x NaN
    into the sums); likewise dk and dv of a key tile with q and the
    cotangent set to NaN in every query tile outside its band. That they
    visit every tile of the band is the comparison with the dense
    reference above. A kernel that walked the causal tiles and only
    masked fails on the first tile past the window."""
    q, k, v, do = _qkv(seq, h, hk, d, b=1)
    n = seq // block

    @jax.jit
    def run(q, k, v, do):
        out, vjp = jax.vjp(lambda *a: pf.flash_attention_pallas(
            *a, True, None, block, block, window), q, k, v)
        return (out,) + vjp(do)

    def poisoned(x, keep):
        tiles = jnp.repeat(jnp.asarray([j in keep for j in range(n)]),
                           block)
        return jnp.where(tiles[None, :, None, None], x, jnp.nan)

    rows = lambda x, i: np.asarray(x[:, i * block:(i + 1) * block])
    for i in range(n):
        keys = _tiles_in_band(i, n, block, window, of_keys=True)
        out, dq, _, _ = run(q, poisoned(k, keys), poisoned(v, keys), do)
        assert np.isfinite(rows(out, i)).all(), ("fwd", i)
        assert np.isfinite(rows(dq, i)).all(), ("dq", i)
        queries = _tiles_in_band(i, n, block, window, of_keys=False)
        _, _, dk, dv = run(poisoned(q, queries), k, v,
                           poisoned(do, queries))
        assert np.isfinite(rows(dk, i)).all(), ("dk", i)
        assert np.isfinite(rows(dv, i)).all(), ("dv", i)
    # the test can fail: without a window the loops walk every causal
    # tile, and the last query tile meets the first key tile's NaN
    keys = _tiles_in_band(n - 1, n, block, window, of_keys=True)
    assert 0 not in keys
    plain = pf.flash_attention_pallas(q, poisoned(k, keys),
                                      poisoned(v, keys), True, None, block,
                                      block)
    assert not np.isfinite(rows(plain, n - 1)).any()


def test_the_window_bites_and_counts_the_querys_own_key():
    q, k, v, _ = _qkv(64, 2, 2)
    plain = flash_attention_reference(q, k, v, causal=True)
    banded = flash_attention_reference(q, k, v, causal=True, window=16)
    assert close(banded[:, :16], plain[:, :16])     # 16 keys: all seen
    assert not close(banded[:, 16:], plain[:, 16:], 1e-3)
    # window 1: the query's own key alone, so the output is its value
    assert close(flash_attention_reference(q, k, v, causal=True, window=1),
                 v)
    with pytest.raises(ValueError, match="causal attention only"):
        flash_attention_reference(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="causal attention only"):
        pf.flash_attention_pallas(q, k, v, False, None, 32, 32, 4)


def test_no_window_is_the_old_program_to_the_bit():
    """``window=None`` traces the program the accepted cells compiled: the
    call without the argument and the call with None are one jaxpr, with
    the kernels' old names; and its result is, bit for bit, that of a
    window too wide to cut anything (32 blocks of 8: two chunks)."""
    q, k, v, do = _qkv(256, 4, 2)

    def both(fn):
        return jax.vjp(fn, q, k, v)

    old = lambda q, k, v: pf.flash_attention_pallas(q, k, v, True, None,
                                                    8, 8)
    new = lambda q, k, v: pf.flash_attention_pallas(q, k, v, True, None,
                                                    8, 8, None)
    wide = lambda q, k, v: pf.flash_attention_pallas(q, k, v, True, None,
                                                     8, 8, 256)
    text = {}
    for name, fn in (("old", old), ("new", new), ("wide", wide)):
        text[name] = str(jax.make_jaxpr(
            lambda q, k, v: jax.vjp(fn, q, k, v)[1](do))(q, k, v))
    assert text["old"] == text["new"]
    assert "flash_win" not in text["new"] and "flash_bwd_dkv" in text["new"]
    assert "flash_win_bwd_dkv" in text["wide"]
    out_new, vjp_new = both(new)
    out_wide, vjp_wide = both(wide)
    assert (np.asarray(out_new) == np.asarray(out_wide)).all()
    for a, b in zip(vjp_new(do), vjp_wide(do)):
        assert (np.asarray(a) == np.asarray(b)).all()


def _walked(steps):
    """The (kept block, streamed block) tiles a backward call's table
    walks, and those it masks."""
    walked, masked = set(), set()
    for row, _, lo, a, b, hi, _, _ in steps.reshape(-1, pf._FIELDS):
        walked |= {(row, j) for j in range(lo, hi)}
        masked |= {(row, j) for j in list(range(lo, a)) + list(range(b, hi))}
    return walked, masked


def test_the_tiles_at_the_cells_shape_by_hand():
    """1 x 16,384 under 4,096 keys at tiles of 512: query tile i sees key
    tiles i - 8 .. i (the first key of tile i - 8 is the one its first
    query no longer sees, the others of that tile it does): 36 + 24 x 9 =
    252 of the 528 causal tiles, for each of the three kernels and each
    of the 28 heads; kernels that walked every causal tile and masked
    would read 528 / 252 = 2.1. The backward calls walk exactly those
    tiles, and mask only the 32 the diagonal cuts and the 24 the band's
    lower edge cuts."""
    per_kernel = sum(len(_tiles_in_band(i, 32, 512, 4096, of_keys=True))
                     for i in range(32))
    assert per_kernel == sum(min(i + 1, 9) for i in range(32)) == 252
    assert per_kernel == sum(
        len(_tiles_in_band(j, 32, 512, 4096, of_keys=False))
        for j in range(32))
    for window, tiles, cut, steps in ((4096, 252, 56, 40),
                                      (None, 528, 32, 48)):
        dq = pf._bwd_steps(pf._dq_spans(32, 32, 512, 512, 0, True, window),
                           16)
        dkv = pf._bwd_steps(pf._dkv_spans(32, 32, 512, 512, 0, True,
                                          window), 16)
        (dq_tiles, dq_cut), (dkv_tiles, dkv_cut) = _walked(dq), _walked(dkv)
        # dq's rows are query tiles, dk/dv's key tiles: the same pairs
        assert dq_tiles == {(j, i) for i, j in dkv_tiles}
        assert dq_cut == {(j, i) for i, j in dkv_cut}
        assert len(dq_tiles) == tiles and len(dq_cut) == cut
        if window is not None:
            assert dq_tiles == {(i, j) for i in range(32) for j in
                                _tiles_in_band(i, 32, 512, window, True)}
        else:
            assert dq_tiles == {(i, j) for i in range(32)
                                for j in range(i + 1)}
        # grid steps of sixteen blocks a step, per (batch, head): every
        # step meets at least one tile it walks
        assert len(dq) // pf._FIELDS == len(dkv) // pf._FIELDS == steps
        for table in (dq, dkv):
            fields = table.reshape(-1, pf._FIELDS)
            assert (fields[:, pf._HI] > fields[:, pf._LO]).all()


@pytest.mark.parametrize("window", [None, 160])
def test_the_backward_of_sixteen_blocks_is_two_kernel_calls(window):
    """512 positions at blocks of 32: one dq call and one dk/dv call
    over the whole sequence, under the names the rooflines read, and no
    partial gradient added up outside them."""
    q, k, v, do = _qkv(512, 4, 2)
    text = str(jax.make_jaxpr(lambda q, k, v: jax.vjp(
        lambda *a: pf.flash_attention_pallas(*a, True, None, 32, 32, window),
        q, k, v)[1](do))(q, k, v))
    stem = "flash" if window is None else "flash_win"
    for name in ("fwd", "bwd_dq", "bwd_dkv"):
        assert text.count(f"name={stem}_{name}") == 1, name
    assert text.count("pallas_call") == 3
    assert "scatter" not in text and "dynamic_update_slice" not in text


def test_the_counter_reads_two_backward_calls_a_traced_layer():
    """``attn.flash.bwd_calls`` counts where the backward calls are made:
    two for each traced backward pass of a layer, none for a forward
    pass alone."""
    reg = telemetry.default_tracer().metrics
    q, k, v, do = _qkv(256, 4, 2)
    layer = lambda x, w: pf.flash_attention_pallas(x, k, v, True, None, 32,
                                                   32, w)
    before = reg.value("attn.flash.bwd_calls") or 0
    jax.make_jaxpr(lambda q: layer(layer(q, None), 64))(q)
    assert (reg.value("attn.flash.bwd_calls") or 0) == before
    jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        layer(layer(q, None), 64) * do)))(q)
    assert reg.value("attn.flash.bwd_calls") == before + 4


def test_the_op_counts_what_took_a_window():
    reg = telemetry.default_tracer().metrics
    before = reg.value("attn.flash.window") or 0
    q, k, v, _ = _qkv(64, 2, 2)
    flash_attention(q, k, v, causal=True)
    assert (reg.value("attn.flash.window") or 0) == before
    flash_attention(q, k, v, causal=True, window=16)
    assert reg.value("attn.flash.window") == before + 1
    assert reg.value("attn.flash.window_size") == 16


# -- the expert layer: a router input of its own, a ReLU gate -----------------

def _expert_operands(e=8, d=16, h=24, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (2, 32, d)),           # x
            jax.random.normal(ks[1], (2, 32, d)),           # router input
            jax.random.normal(ks[2], (d, e)),
            0.3 * jax.random.normal(ks[3], (e, d, h)),
            0.3 * jax.random.normal(ks[4], (e, d, h)),
            0.3 * jax.random.normal(ks[5], (e, h, d)))


def _ref_cfg(**model):
    from benchmark import manifest
    cfg = copy.deepcopy(manifest.load_json(
        ROOT, "benchmark/configs/smallthinker_21b_a3b_ep4_l4_train.json"))
    cfg["model"].update(
        hidden_size=64, head_dim=16, num_attention_heads=6,
        num_key_value_heads=2, moe_ffn_hidden_size=48,
        moe_num_primary_experts=2, expert_share=[1, 4],
        moe_num_active_primary_experts=2, vocab_size=128,
        sliding_window_size=16, torch_dtype="float32")
    cfg["model"].update(model)
    cfg["init_scale"] = 0.3
    return cfg


def test_the_four_shares_add_up_to_the_uncut_references_whole_layer():
    from benchmark.reference import smallthinker_ref as ref
    x, r, gw, wg, wu, wd = _expert_operands()
    lw = {"wr": gw, "eg": wg, "eu": wu, "ed": wd}
    whole_model = dict(_ref_cfg()["model"], moe_num_primary_experts=8,
                       expert_share=[0, 1])
    whole = jnp.stack([ref.held_experts(u, on, lw, whole_model)
                       for u, on in zip(x, r)])
    total, rows = 0, []
    for share in range(4):
        lo, n = share * 2, 2
        out, seen, _ = moe.moe_share_forward(
            x, gw, wg[lo:lo + n], wu[lo:lo + n], wd[lo:lo + n], 2, lo, True,
            moe.route_softmax, "relu", r)
        # each share is the reference told the same share
        part = dict(whole_model, moe_num_primary_experts=n,
                    expert_share=[share, 4])
        cut = {key: (w[lo:lo + n] if key != "wr" else w)
               for key, w in lw.items()}
        assert close(out, jnp.stack([ref.held_experts(u, on, cut, part)
                                     for u, on in zip(x, r)]))
        total, rows = total + out, rows + list(np.asarray(seen))
    assert close(total, whole)
    assert sum(rows) == 2 * 32 * 2              # every (token, choice) once


def test_a_router_input_of_its_own_changes_the_selection_and_takes_the_gradient():
    x, r, gw, wg, wu, wd = _expert_operands()
    share = lambda x, r=None, act="silu": moe.moe_share_forward(
        x, gw, wg[:2], wu[:2], wd[:2], 2, 0, True, moe.route_softmax, act, r)
    plain, rows, _ = share(x)
    same, rows_same, _ = share(x, x)
    assert (np.asarray(plain) == np.asarray(same)).all()    # x is the default
    other, rows_other, _ = share(x, r)
    assert list(np.asarray(rows_other)) != list(np.asarray(rows))
    assert not close(other, plain, 1e-3)
    # the router's gradient flows to the tensor it read: with r given, x
    # gets the experts' part alone
    weight = jax.random.normal(jax.random.PRNGKey(1), plain.shape)
    loss = lambda x, r: jnp.sum(share(x, r)[0] * weight)
    dx, dr = jax.grad(loss, (0, 1))(x, r)
    assert float(jnp.abs(dr).max()) > 0
    both = jax.grad(lambda x: jnp.sum(share(x)[0] * weight))(x)
    split_x, split_r = jax.grad(loss, (0, 1))(x, x)
    assert close(split_x + split_r, both)
    assert not close(split_x, both, 1e-3)


def test_relu_beside_silu_by_hand():
    x, _, gw, wg, wu, wd = _expert_operands(e=2)
    gates = jax.nn.softmax(x @ gw, -1)          # top-2 of 2: every expert
    for name, act in (("relu", jax.nn.relu), ("silu", jax.nn.silu)):
        out, _, _ = moe.moe_share_forward(x, gw, wg, wu, wd, 2, 0, True,
                                          moe.route_softmax, name)
        want = sum(gates[..., e:e + 1]
                   * ((act(x @ wg[e]) * (x @ wu[e])) @ wd[e])
                   for e in range(2))
        assert close(out, want), name
    with pytest.raises(ValueError, match="there are: silu, relu"):
        moe.moe_share_forward(x, gw, wg, wu, wd, 2, 0, True,
                              moe.route_softmax, "gelu")
    with pytest.raises(ValueError, match="there are: silu, relu"):
        nn.MoEShareLayer(16, 24, 8, 2, activation="gelu")


def test_the_layer_takes_its_router_input_and_its_activation():
    paddle.seed(3)
    layer = nn.MoEShareLayer(16, 24, 8, 2, share=(1, 4), activation="relu")
    silu = nn.MoEShareLayer(16, 24, 8, 2, share=(1, 4))
    for a, b in zip(silu.parameters(), layer.parameters()):
        a._replace(b._value)
    reg = telemetry.default_tracer().metrics
    relus = reg.value("moe.expert.relu") or 0
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.normal(size=(2, 32, 16)).astype(np.float32))
    r = paddle.to_tensor(rng.normal(size=(2, 32, 16)).astype(np.float32),
                         stop_gradient=False)
    plain = layer(x)
    assert reg.value("moe.expert.relu") == relus + 1
    assert not close(silu(x)._value, plain._value, 1e-3)
    assert reg.value("moe.expert.relu") == relus + 1        # silu counts none
    routed = layer(x, router_input=r)
    assert not close(routed._value, plain._value, 1e-3)
    routed.sum().backward()
    assert r.grad is not None and float(jnp.abs(r.grad._value).max()) > 0
    assert layer.routing_counts()["rows_routed"] == 2 * 2 * 32 * 2


# -- the model ----------------------------------------------------------------

def test_recompute_trains_the_same_and_the_counters_say_what_ran():
    ids = np.random.default_rng(0).integers(0, 128, (2, 64), dtype=np.int32)
    reg = telemetry.default_tracer().metrics
    losses = {}
    for rc in (False, True):
        paddle.seed(0)
        model = SmallThinkerForCausalLM(smallthinker_tiny(use_recompute=rc))
        windows = [layer.self_attn.window for layer in model.model.layers]
        rotary = [layer.self_attn.rotary for layer in model.model.layers]
        assert windows == [None, 16, 16, 16]
        assert rotary == [False, True, True, True]
        assert model.lm_head is not None        # untied
        seen = {name: reg.value(name) or 0 for name in (
            "attn.flash.window", "moe.route.pre_attention",
            "moe.expert.relu", "recompute.regions",
            "recompute.regions_keeping")}
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
        t = paddle.to_tensor(ids)
        losses[rc] = [float(step(t, t)._value) for _ in range(3)]
        assert losses[rc][2] < losses[rc][0]
        counts = model.routing_counts()
        # steps x layers x tokens x top-2
        assert counts["rows_routed"] == 3 * 4 * 2 * 64 * 2
        assert 0 < counts["rows_max_expert"] <= counts["rows_held"] \
            < counts["rows_routed"]
        # a trace of the step: three layers took a window, four routed
        # before attention, four gated with ReLU
        assert reg.value("attn.flash.window") >= seen["attn.flash.window"] + 3
        assert reg.value("moe.route.pre_attention") \
            >= seen["moe.route.pre_attention"] + 4
        assert reg.value("moe.expert.relu") >= seen["moe.expert.relu"] + 4
        # and under recomputation each layer is a region that keeps the
        # flash kernels' outputs by name; without it there is no region
        for name in ("recompute.regions", "recompute.regions_keeping"):
            grew = (reg.value(name) or 0) - seen[name]
            assert (grew >= 4 and grew % 4 == 0) if rc else grew == 0
    assert np.allclose(losses[True], losses[False], rtol=1e-5)
    assert reg.snapshot()["counters"]["moe.rows_routed"] \
        == counts["rows_routed"]
    assert reg.value("attn.flash.window_size") == 16


def test_the_layers_named_scopes_reach_the_compiled_step():
    paddle.seed(0)
    model = SmallThinkerForCausalLM(smallthinker_tiny())
    ids = jnp.zeros((2, 64), jnp.int32)
    params = [p._value for p in model.parameters()]
    buffers = [b._value for _, b in model.named_buffers()]
    from paddle_tpu.jit import _wrap_tree, functional_call

    def loss(params):
        out, _ = functional_call(model, params, buffers, (ids,))
        return model.loss(_wrap_tree(out), paddle.to_tensor(ids))._value
    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    for scope in ("embed", "layer0/attn_global", "layer0/moe",
                  "layer1/attn_window", "layer3/attn_window", "final_norm",
                  "lm_head", "loss",
                  # the router's own step, beside the experts it is
                  # computed with
                  "jvp(layer0/moe)/router/dot_general",
                  "jvp(layer3/moe)/router/dot_general"):
        assert scope in text, scope
    assert "layer0/attn_window" not in text
    assert "layer1/attn_global" not in text


def test_the_config_refuses_what_the_decoder_has_not():
    with pytest.raises(ValueError, match="sliding_window_layout has to"):
        SmallThinkerConfig(num_hidden_layers=3)
    with pytest.raises(ValueError, match="rope_layout has to"):
        smallthinker_tiny(rope_layout=(0, 1, 2, 1))
    with pytest.raises(ValueError, match="softmax"):
        smallthinker_tiny(moe_primary_router_apply_softmax=False)
    assert smallthinker_tiny().window(0) is None
    assert smallthinker_tiny().window(2) == 16
    assert SmallThinkerConfig().window(51) == 4096


def _program_against_reference(cfg, seed=5, steps=1, lr=1e-6):
    """The program's model with the benchmark's seeded leaves (gains moved
    off one) through ``jit.TrainStep``: its logits, each step's loss,
    every leaf's first gradient as AdamW got it and every leaf after the
    steps; the reference's leaves as they started."""
    from benchmark import weights as W
    from benchmark.families import lm_smallthinker as fam
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
    from benchmark.reference import smallthinker_ref as ref
    cfg = _ref_cfg()
    return (cfg, ref) + _program_against_reference(cfg, steps=3, lr=1e-3)


def test_logits_loss_and_every_leafs_gradient_against_the_plain_reference(
        against_reference, monkeypatch):
    """Both sides are float32 and differ in the order of their sums alone:
    1e-4 of a leaf's largest gradient is a hundred roundings. The
    sequence (64) is four windows (16) long, so the band bites in the
    three window layers."""
    cfg, ref, ids, ref_params, logits, losses, grads, _ = against_reference
    ref_value, ref_grads = ref.loss_and_grads(ref_params, ids, cfg)
    assert losses[0] == pytest.approx(ref_value, rel=1e-5)
    assert set(grads) == set(ref_grads) and len(grads) == 43
    for name, want in ref_grads.items():
        assert float(jnp.abs(want).max()) > 0, name
        assert close(grads[name], want, 1e-4), name
    for row in range(2):
        want = ref.sequence_logits_of(ref_params, jnp.asarray(ids[row]),
                                      cfg["model"])
        assert close(logits[row], want, 1e-5)
    # the window was part of it: the reference without it reads another
    # loss
    wide = dict(cfg, model=dict(cfg["model"], sliding_window_size=64))
    assert abs(ref.loss_and_grads(ref_params, ids, wide)[0] - ref_value) \
        > 1e-4 * ref_value
    # and so was the router's input: routed on what the experts read, the
    # reference reads another loss
    held = ref.held_experts
    monkeypatch.setattr(ref, "held_experts",
                        lambda u, on, *a, **k: held(u, u, *a, **k))
    assert abs(ref.loss_and_grads(ref_params, ids, cfg)[0] - ref_value) \
        > 1e-4 * ref_value


def test_three_adamw_steps_against_the_plain_reference(against_reference):
    """Three steps at lr 1e-3 (a thousand times the cell's, so that the
    change is no rounding): each step's loss to 1e-5 and every leaf after
    the third to 1e-5 of its largest entry; Adam divides by sqrt(v), so a
    gradient entry near zero turns a rounding into a step of another
    sign, which moves an entry by 2e-3 at most and the tolerance sits on
    the leaf's largest entry (0.3 x 3 sigma and more)."""
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


@pytest.mark.parametrize("window,rotary", [(0, 0), (1, 1), (0, 1), (1, 0)])
def test_one_layer_of_each_kind_against_the_plain_reference(window, rotary):
    """A single layer of each kind the two layouts can name: global or
    under the window, bare or rotary. Tolerances as above."""
    from benchmark.reference import smallthinker_ref as ref
    cfg = _ref_cfg(num_hidden_layers=1, sliding_window_layout=[window],
                   rope_layout=[rotary])
    ids, ref_params, logits, losses, grads, _ = \
        _program_against_reference(cfg, seed=9)
    ref_value, ref_grads = ref.loss_and_grads(ref_params, ids, cfg)
    assert losses[0] == pytest.approx(ref_value, rel=1e-5)
    for name, want in ref_grads.items():
        assert close(grads[name], want, 1e-4), name
    assert close(logits[0], ref.sequence_logits_of(
        ref_params, jnp.asarray(ids[0]), cfg["model"]), 1e-5)
    # the kind matters: the other window, or the other embedding, reads
    # another loss
    for key, flipped in (("sliding_window_layout", [1 - window]),
                         ("rope_layout", [1 - rotary])):
        other = dict(cfg, model=dict(cfg["model"], **{key: flipped}))
        assert abs(ref.loss_and_grads(ref_params, ids, other)[0]
                   - ref_value) > 1e-5 * ref_value, key
