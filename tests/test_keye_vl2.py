"""Keye-VL-2.0's language model on the CPU at a small size: the learned
sparse attention's kernels (interpret mode) against ``jax.numpy``, the
selection against ``lax.top_k`` with planted ties, the expert layer that
is told its share, and the whole model against the benchmark's plain
reference (``benchmark/reference/keye_vl2_ref.py``)."""
import copy
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.jit import _wrap_tree, functional_call
from paddle_tpu.models import KeyeVL2ForCausalLM, keye_vl2_tiny
from paddle_tpu.ops import moe
from paddle_tpu.ops.moe import moe_share_forward
from paddle_tpu.ops.pallas import sparse_attention as sa

B, H, HK, S, D, HI, DI, TOPK = 2, 4, 2, 64, 32, 4, 16, 16
SCALE = D ** -0.5


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    shapes = [(B, H, S, D), (B, HK, S, D), (B, HK, S, D), (B, HI, S, DI),
              (B, S, DI), (B, S, HI), (B, H, S, D)]
    return [jax.random.normal(k, s, jnp.float32)
            for k, s in zip(ks, shapes)]


def close(a, b, tol=1e-5):
    return float(jnp.max(jnp.abs(a - b))) <= tol * (
        1.0 + float(jnp.max(jnp.abs(b))))


# -- the kernels, one at a time -----------------------------------------------

def test_indexer_scores_kernel(operands):
    _, _, _, qi, ki, w, _ = operands
    got = sa.indexer_scores(qi, ki, w, 32, 32)
    want = sa.indexer_scores_reference(qi, ki, w)
    tri = jnp.tril(jnp.ones((S, S), bool))
    assert close(jnp.where(tri, got, 0), jnp.where(tri, want, 0))
    # a tile that lies wholly above the diagonal is not computed
    assert float(jnp.abs(got[:, :32, 32:]).max()) == 0.0


def brute_select(x, topk):
    x = np.asarray(x) + 0.0
    out = np.zeros(x.shape, np.int8)
    for b in range(x.shape[0]):
        for t in range(x.shape[1]):
            best = np.argsort(-x[b, t, :t + 1], kind="stable")[:topk]
            out[b, t, best] = 1
    return out


@pytest.mark.parametrize("ties", ["none", "halves", "zeros", "all_equal"])
def test_selection_is_the_exact_topk_ties_to_the_lower_index(operands, ties):
    _, _, _, qi, ki, w, _ = operands
    x = sa.indexer_scores_reference(qi, ki, w)
    if ties == "halves":            # many equal values, -0.0 among them
        x = jnp.round(x * 2) / 2
    elif ties == "zeros":           # a relu that mostly reads nought
        x = jnp.maximum(x - 2.0, 0.0) * jnp.where(x > 4, -1.0, 1.0)
    elif ties == "all_equal":
        x = jnp.full_like(x, 1.5)
    kernel = np.asarray(sa.topk_select(x, TOPK))
    assert (kernel == brute_select(x, TOPK)).all()
    assert (kernel == np.asarray(sa.topk_select_reference(x, TOPK))).all()
    want = np.minimum(np.arange(S) + 1, TOPK)
    assert (kernel.sum(-1) == want[None]).all()


@pytest.mark.parametrize("h,hk,bq,bk,topk", [
    (4, 2, 32, 32, 16),         # two query heads a key head, square tiles
    (4, 2, 16, 32, 16),         # a query block shorter than the key block
    (4, 2, 32, 16, 16),         # and longer: two key tiles on the diagonal
    (4, 4, 32, 32, 16),         # a group of 1
    (8, 1, 32, 32, 16),         # a group of 8
    (4, 2, 16, 16, 16),         # the first row of tiles keeps every causal
                                # key (t < topk), the others do not
    (3, 1, 32, 32, 24),         # heads that are no power of two
    (8, 1, 8, 32, 16),          # eight heads a key head in one dk / dv
                                # step; a key tile's column zeroed above
                                # its first causal query tile (four tiles)
    (4, 2, 32, 8, 16),          # eight key blocks a query tile; the first
                                # one's loop stops at the fourth
])
def test_attention_kernels_forward_target_and_backward(h, hk, bq, bk, topk):
    from paddle_tpu.utils import telemetry
    reg = telemetry.default_tracer().metrics
    steps = lambda: reg.value("attn.sparse.grid_steps") or 0
    before = steps()
    ks = jax.random.split(jax.random.PRNGKey(h * 100 + hk), 7)
    q, do = (jax.random.normal(k, (B, h, S, D)) for k in ks[:2])
    k, v = (jax.random.normal(k, (B, hk, S, D)) for k in ks[2:4])
    qi, ki, w = (jax.random.normal(k, s) for k, s in zip(
        ks[4:], [(B, HI, S, DI), (B, S, DI), (B, S, HI)]))
    scores = sa.indexer_scores_reference(qi, ki, w)
    mask = sa.topk_select(scores, topk)
    out, lse = sa.sparse_attn_fwd(q, k, v, mask, SCALE, bq, bk)
    want, p = sa.sparse_attention_reference(q, k, v, mask, SCALE)
    assert close(out, want)
    # the indexer's loss by its kernel; its gradient w.r.t. the scores is
    # the fused backward's fourth output
    loss, rows = sa.indexer_loss(q, k, lse, mask, scores, SCALE, bq, bk)
    want_loss, want_grad = jax.value_and_grad(sa._indexer_kl)(
        scores, p.mean(1), mask)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    *got, d_scores = sa.sparse_attn_bwd(q, k, v, out, lse, do, mask, scores,
                                        rows, SCALE, bq, bk)
    assert close(d_scores / (B * S), want_grad, 1e-5)
    # nothing outside the selection, the tiles above the diagonal included
    assert float(jnp.abs(jnp.where(mask > 0, 0.0, d_scores)).max()) == 0.0
    grads = jax.grad(lambda *a: jnp.sum(
        sa.sparse_attention_reference(*a, mask, SCALE)[0] * do),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, grads):
        assert close(a, b)
    # the forward and dq kernels take a step a (row, query tile, head), the
    # loss's and dk / dv's a step a (row, causal tile): none above the
    # diagonal
    assert steps() - before == 2 * B * (S // bq) * h + 2 * B * causal_tiles(
        bq, bk)


def causal_tiles(bq, bk):
    return sum(kj * bk <= qi * bq + bq - 1
               for qi in range(S // bq) for kj in range(S // bk))


def test_the_backward_pass_counts_the_target_it_folds_in(operands):
    """One ``attn.sparse.target_in_backward`` a traced layer's backward
    pass, none for a forward pass alone; and the backward pass is three
    kernels (scores, dq, dkv with the target), no fourth for the loss's
    gradient. ``attn.sparse.grid_steps`` takes the steps of the four
    tile-walking calls."""
    from paddle_tpu.utils import telemetry
    q, k, v, qi, ki, w, do = operands
    reg = telemetry.default_tracer().metrics
    count = lambda: reg.value("attn.sparse.target_in_backward") or 0
    steps = lambda: reg.value("attn.sparse.grid_steps") or 0

    def loss(*a):
        o, li = sa.learned_sparse_attention(*a, TOPK, SCALE)
        return jnp.sum(o * do) + li
    before = count()
    jax.make_jaxpr(loss)(q, k, v, qi, ki, w)
    assert count() == before
    walked = steps()
    text = str(jax.make_jaxpr(jax.grad(loss, argnums=tuple(range(6))))(
        q, k, v, qi, ki, w))
    assert count() == before + 1
    # blocks of 512 cut to the sequence: one query tile, one causal tile;
    # forward and dq a step a (row, head), the loss and dk / dv one a row
    block = min(512, S)
    assert steps() - walked == 2 * B * (S // block) * H \
        + 2 * B * causal_tiles(block, block)
    # forward: scores, selection, attention, rows; backward: the three
    assert text.count("pallas_call[") == 7
    for name in ("sparse_attn_bwd_dq", "sparse_attn_bwd_dkv",
                 "indexer_loss_rows"):
        assert text.count(f"name={name}") == 1
    assert "indexer_loss_grad" not in text


def _both(operands, topk):
    q, k, v, qi, ki, w, do = operands

    def run(fn):
        def loss(*a):
            o, li = fn(*a, topk, SCALE)
            return jnp.sum(o * do) + 3.0 * li
        return jax.value_and_grad(loss, argnums=tuple(range(6)))(
            q, k, v, qi, ki, w)
    return run(sa.learned_sparse_attention), \
        run(sa.learned_sparse_attention_reference)


def test_the_whole_function_against_jax_numpy_autodiff(operands):
    (l1, g1), (l2, g2) = _both(operands, TOPK)
    assert abs(float(l1) - float(l2)) < 1e-4 * abs(float(l2))
    for a, b in zip(g1, g2):
        assert close(a, b, 1e-4)


def test_a_sequence_no_longer_than_topk_is_dense_causal_attention(operands):
    q, k, v, qi, ki, w, _ = operands
    out, _ = sa.learned_sparse_attention(q, k, v, qi, ki, w, S, SCALE)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), jnp.int8)),
                              (B, S, S))
    dense, _ = sa.sparse_attention_reference(q, k, v, causal, SCALE)
    assert close(out, dense)
    # and the selection is the causal triangle itself, bit for bit
    scores = sa.indexer_scores(qi, ki, w)
    assert (np.asarray(sa.topk_select(scores, S)) == np.asarray(causal)).all()
    assert (np.asarray(sa.topk_select(scores, 4 * S))
            == np.asarray(causal)).all()


def test_the_two_sides_of_the_gradient_do_not_mix(operands):
    """q, k, v learn from the output alone; the indexer's operands from
    its loss alone."""
    q, k, v, qi, ki, w, do = operands

    def grads(w_out, w_loss):
        def loss(*a):
            o, li = sa.learned_sparse_attention(*a, TOPK, SCALE)
            return w_out * jnp.sum(o * do) + w_loss * li
        return jax.grad(loss, argnums=tuple(range(6)))(q, k, v, qi, ki, w)
    from_loss, from_out = grads(0.0, 1.0), grads(1.0, 0.0)
    for g in from_loss[:3] + from_out[3:]:
        assert float(jnp.abs(g).max()) == 0.0
    for g in from_out[:3] + from_loss[3:]:
        assert float(jnp.abs(g).max()) > 0.0


# -- the expert layer that is told its share ----------------------------------

def dense_experts(x, gw, wg, wu, wd, k, held):
    t = x.reshape(-1, x.shape[-1])
    p = jax.nn.softmax(t @ gw, -1)
    top_p, top_i = jax.lax.top_k(p, k)
    g = top_p / top_p.sum(-1, keepdims=True)
    out = 0
    for e in held:
        ge = (g * (top_i == e)).sum(-1)
        out = out + ge[:, None] * (
            (jax.nn.silu(t @ wg[e]) * (t @ wu[e])) @ wd[e])
    return out.reshape(x.shape)


@pytest.fixture(scope="module")
def experts():
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    d, h, e = 16, 24, 8
    return (jax.random.normal(ks[0], (2, 32, d)),
            jax.random.normal(ks[1], (d, e)),
            jax.random.normal(ks[2], (e, d, h)) * 0.3,
            jax.random.normal(ks[3], (e, d, h)) * 0.3,
            jax.random.normal(ks[4], (e, h, d)) * 0.3)


@pytest.mark.parametrize("shares", [4, 8])
def test_the_shares_outputs_add_up_to_the_uncut_layer(experts, shares):
    x, gw, wg, wu, wd = experts
    whole = dense_experts(x, gw, wg, wu, wd, 2, range(8))
    n, total, rows = 8 // shares, 0, []
    for i in range(shares):
        lo = i * n
        out, r, _ = moe_share_forward(x, gw, wg[lo:lo + n], wu[lo:lo + n],
                                      wd[lo:lo + n], 2, lo)
        assert close(out, dense_experts(x, gw, wg, wu, wd, 2,
                                        range(lo, lo + n)))
        total, rows = total + out, rows + list(np.asarray(r))
    assert close(total, whole)
    assert sum(rows) == 2 * 32 * 2          # every (token, choice) once


@pytest.mark.parametrize("favoured", [2, 3])
def test_a_share_is_dropless_whatever_the_routing(experts, favoured):
    """Every token sent to the first experts, of which two are held: up
    to four times the even share, through chunks sized for twice it (64
    rows). With three favoured a held expert's rows straddle the chunks'
    border."""
    x, gw, wg, wu, wd = experts
    x = x.at[..., 0].set(1.0)
    gw = gw.at[0, :favoured].add(50.0)
    out, rows, _ = moe_share_forward(x, gw, wg[:2], wu[:2], wd[:2], 2, 0)
    if favoured == 2:
        assert list(np.asarray(rows)) == [64, 64]
    else:
        assert int(rows.sum()) > 64 and 0 < int(rows[0]) < 64
    assert close(out, dense_experts(x, gw, wg, wu, wd, 2, range(2)))


def test_the_shares_gradients(experts):
    x, gw, wg, wu, wd = experts

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) ** 2),
                        argnums=tuple(range(5)))(x, gw, wg[2:4], wu[2:4],
                                                 wd[2:4])
    got = grads(lambda x, gw, a, b, c: moe_share_forward(
        x, gw, a, b, c, 2, 2)[0])
    pad = lambda a, full: jnp.concatenate([full[:2], a, full[4:]])
    want = grads(lambda x, gw, a, b, c: dense_experts(
        x, gw, pad(a, wg), pad(b, wu), pad(c, wd), 2, range(2, 4)))
    for a, b in zip(got, want):
        assert close(a, b, 1e-4)


# -- the share's row movement: blocks up to the held rows ----------------------

BLOCK, R = 8, 40
LIVES = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, R]


@pytest.fixture(scope="module")
def movement():
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    return (jax.random.normal(ks[0], (12, 16)),             # src / dst
            jax.random.randint(ks[1], (R,), 0, 12),         # tok, repeated
            jax.random.normal(ks[2], (R, 16)))              # rows


def whole_take(src, tok, live):
    """The whole-chunk gather with its cut, as the share made it before
    the walk."""
    held = (jnp.arange(tok.shape[0]) < live)[:, None]
    return jnp.where(held, jnp.take(src, tok, axis=0), 0.0)


def whole_add(dst, tok, rows, live):
    held = (jnp.arange(tok.shape[0]) < live)[:, None]
    return dst + jnp.zeros_like(dst).at[tok].add(jnp.where(held, rows, 0.0))


@pytest.mark.parametrize("r", [R, R - 4])       # 8 does not divide 36
@pytest.mark.parametrize("live", LIVES)
def test_take_and_add_walk_blocks_up_to_the_live_rows(movement, live, r):
    src, tok, rows = movement
    tok, rows, live = tok[:r], rows[:r], min(live, r)
    got = jax.jit(moe._take_rows, static_argnums=3)(src, tok, live, BLOCK)
    assert (np.asarray(got) == np.asarray(whole_take(src, tok, live))).all()
    # what lies past the live rows is never read: not a NaN gets through
    dead = rows.at[live:].set(jnp.nan)
    got = jax.jit(moe._add_rows, static_argnums=4)(
        src, tok, dead, live, BLOCK)
    assert close(got, whole_add(src, tok, rows, live), 1e-6)


@pytest.mark.parametrize("live", LIVES)
def test_take_and_add_are_each_others_transpose(movement, live):
    """The vjp of the whole-chunk gather is the walk's add into zeros and
    the vjp of the whole-chunk scatter-add the walk's take: what
    ``_share_experts_bwd`` relies on."""
    src, tok, rows = movement
    _, vjp = jax.vjp(lambda s: whole_take(s, tok, live), src)
    assert close(vjp(rows)[0], moe._add_rows(
        jnp.zeros_like(src), tok, rows, live, BLOCK), 1e-6)
    _, vjp = jax.vjp(lambda d, a: whole_add(d, tok, a, live), src, rows)
    d_dst, d_rows = vjp(src)
    assert close(d_dst, src)
    assert close(d_rows, moe._take_rows(src, tok, live, BLOCK))


def whole_chunk_share(x, gate_w, w_gate, w_up, w_down, top_k, first_expert):
    """``moe_share_forward`` as it was before its row movement walked
    blocks: every chunk gathers all its rows, cuts the dead ones out and
    scatter-adds them all into a fresh array; differentiated by jax."""
    b, s, d = x.shape
    tokens = x.reshape(b * s, d)
    t, e, n_held = b * s, gate_w.shape[1], w_gate.shape[0]
    n_rows = t * top_k
    top_i, gates = moe.route_softmax(tokens @ gate_w, top_k)
    local = top_i.reshape(n_rows) - first_expert
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.bincount(key, length=n_held + 1)[:n_held].astype(jnp.int32)
    row_chunk = min(n_rows, -(-int(2 * n_rows * n_held / e) // 16) * 16)
    n_chunks = -(-n_rows // row_chunk)
    order = jnp.pad(order, (0, n_chunks * row_chunk - n_rows),
                    constant_values=n_rows - 1)
    flat_gates, ends = gates.reshape(n_rows), jnp.cumsum(sizes)

    def chunk(lo):
        idx = jax.lax.dynamic_slice(order, (lo,), (row_chunk,))
        tok = idx // top_k
        gs = jnp.clip(ends, lo, lo + row_chunk) \
            - jnp.clip(ends - sizes, lo, lo + row_chunk)
        held = (jnp.arange(row_chunk) < ends[-1] - lo)[:, None]
        rows = lambda a: jnp.where(held, a, 0.0)
        dot = lambda a, w: rows(jax.lax.ragged_dot(a, w, gs))
        xs = rows(jnp.take(tokens, tok, axis=0))
        ys = dot(jax.nn.silu(dot(xs, w_gate)) * dot(xs, w_up), w_down)
        return jnp.zeros((t, d)).at[tok].add(
            ys * jnp.take(flat_gates, idx)[:, None])

    def step(out, lo):
        return jax.lax.cond(lo < ends[-1], lambda o: o + chunk(lo),
                            lambda o: o, out), None

    starts = jnp.arange(n_chunks, dtype=jnp.int32) * row_chunk
    out, _ = jax.lax.scan(step, jnp.zeros((t, d)), starts)
    return out.reshape(b, s, d), jnp.clip(ends[-1] - starts, 0, row_chunk)


@pytest.mark.parametrize("routing", ["none", "even", "nearly_full", "all"])
def test_the_walk_is_the_whole_chunk_share_and_counts_its_blocks(
        experts, routing, monkeypatch):
    """Two of eight experts held and 128 routed rows: chunks of 64. No
    held row at all, an even share (about 32), 60 (1.9 x the share: one
    chunk nearly full) and every row (two full chunks)."""
    monkeypatch.setattr(moe, "_ROW_BLOCK", BLOCK)
    x, gw, wg, wu, wd = experts
    if routing != "even":
        # a token whose x[0] is 1 picks the two held experts, one whose
        # x[1] is 1 never picks either
        n = {"none": 0, "nearly_full": 30, "all": 64}[routing]
        picks = (jnp.arange(64) < n).reshape(2, 32).astype(x.dtype)
        x = x.at[..., 0].set(picks).at[..., 1].set(1.0 - picks)
        gw = gw.at[0, :2].add(50.0).at[1, :2].add(-50.0)
    args = (x, gw, wg[:2], wu[:2], wd[:2])
    want, lives = whole_chunk_share(*args, 2, 0)
    lives = np.asarray(lives)
    got, rows, walked = moe_share_forward(*args, 2, 0)
    assert int(rows.sum()) == lives.sum()
    if routing == "even":
        assert 16 < lives[0] < 48 and lives[1] == 0
    else:
        assert list(lives) == {"none": [0, 0], "nearly_full": [60, 0],
                               "all": [64, 64]}[routing]
    assert close(got, want)
    assert int(walked) == BLOCK * sum(-(-int(n) // BLOCK) for n in lives)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a, 2, 0)[0] ** 2),
                        argnums=tuple(range(5)))(*args)
    for a, b in zip(grads(moe_share_forward), grads(whole_chunk_share)):
        assert close(a, b, 1e-4)


def test_the_layer_counts_its_rows_and_refuses_an_uneven_share():
    paddle.seed(3)
    layer = nn.MoEShareLayer(16, 24, 8, 2, share=(1, 4))
    assert layer.w_gate.shape == [2, 16, 24] and layer.first_expert == 2
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(2, 32, 16)).astype(np.float32))
    layer(x)
    layer(x)
    counts = layer.routing_counts()
    assert counts["rows_routed"] == 2 * 2 * 32 * 2
    assert 0 < counts["rows_max_expert"] <= counts["rows_held"] \
        < counts["rows_routed"]
    # two calls' one chunk of 64 rows each, walked as the one block it is
    assert counts["rows_held"] < counts["rows_walked"] == 2 * 64
    # a counter is two words: it carries out of the low one exactly
    low = layer.rows._value[0].at[-1].add(2 ** 30 - 100)
    layer.rows._replace(layer.rows._value.at[0].set(low))
    layer(x)
    assert layer.routing_counts()["rows_routed"] == 2 ** 30 - 100 \
        + 3 * 2 * 32 * 2
    assert int(layer.rows._value[1, -1]) == 1
    with pytest.raises(ValueError, match="does not divide"):
        nn.MoEShareLayer(16, 24, 8, 2, share=(0, 3))
    with pytest.raises(NotImplementedError, match="MoEShareLayer"):
        moe = nn.MoELayer(16, 24, 4, dispatch_mode="ragged")
        moe._ep_sharding = lambda: object()
        moe(x)


# -- the model ----------------------------------------------------------------

def test_recompute_trains_the_same():
    ids = np.random.default_rng(0).integers(0, 128, (2, 64), dtype=np.int32)
    losses = {}
    for rc in (False, True):
        paddle.seed(0)
        model = KeyeVL2ForCausalLM(keye_vl2_tiny(use_recompute=rc))
        opt = optimizer.AdamW(learning_rate=1e-3,
                              parameters=model.parameters())
        step = paddle.jit.TrainStep(model, lambda o, l: model.loss(o, l), opt)
        t = paddle.to_tensor(ids)
        losses[rc] = [float(step(t, t)._value) for _ in range(3)]
        assert losses[rc][2] < losses[rc][0]
        counts = model.routing_counts()
        assert counts["rows_routed"] == 3 * 2 * 2 * 64 * 2   # steps x layers
    assert np.allclose(losses[True], losses[False], rtol=1e-5)
    # the model's counters reach the registry when it is exported
    from paddle_tpu.utils import telemetry
    reg = telemetry.default_tracer().metrics
    assert reg.snapshot()["counters"]["moe.rows_routed"] \
        == counts["rows_routed"] == reg.value("moe.rows_routed")
    assert reg.value("moe.rows_walked") == counts["rows_walked"] \
        >= counts["rows_held"]
    assert reg.value("attn.sparse.kernel") >= 2


def _benchmark_cfg():
    import sys
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark import manifest
    cfg = copy.deepcopy(manifest.load_json(
        root, "benchmark/configs/keye_vl2_30b_a3b_ep8_l4_train.json"))
    m = cfg["model"]
    m.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=32, moe_intermediate_size=48,
             num_experts=2, num_local_experts=2, expert_share=[1, 4],
             num_experts_per_tok=2, vocab_size=128, torch_dtype="float32")
    m["sa_config"].update(indexer_num_heads=4, indexer_head_dim=16, topk=16)
    cfg["init_scale"] = 0.3
    return cfg


@pytest.fixture(scope="module")
def against_reference():
    """The program's model with the benchmark's seeded leaves (gains moved
    off one), its loss and gradients, and the plain reference's."""
    from benchmark import weights as W
    from benchmark.families import lm_keye_vl2 as fam
    from benchmark.reference import keye_vl2_ref as ref
    cfg = _benchmark_cfg()
    model, names = fam.build_trainable(cfg)
    named = dict(model.named_parameters())
    seeded = W.Leaves(fam, cfg, 5)
    for name, shape in seeded.shapes.items():
        leaf = seeded.make(name)
        if len(shape) == 1:
            leaf = leaf + 0.1 * jax.random.normal(
                jax.random.PRNGKey(W.leaf_tag(name)), shape)
        named[names[name]]._replace(leaf)
    ids = np.random.default_rng(0).integers(0, 128, (2, 64), dtype=np.int32)
    order = [n for n, _ in model.named_parameters()]
    buffers = [b._value for _, b in model.named_buffers()]

    def loss(params, with_indexer_loss=True):
        out, _ = functional_call(model, params, buffers, (jnp.asarray(ids),))
        if not with_indexer_loss:
            out = (out[0], jnp.zeros_like(out[1]))
        return model.loss(_wrap_tree(out), paddle.to_tensor(ids))._value

    params = [p._value for p in model.parameters()]
    value, grads = jax.value_and_grad(loss)(params)
    no_aux = jax.grad(lambda p: loss(p, False))(params)
    ref_params = {n: named[names[n]]._value for n in seeded.shapes}
    ref_value, ref_grads = ref.loss_and_grads(ref_params, ids, cfg)
    by_leaf = lambda g: {n: g[order.index(names[n])] for n in seeded.shapes}
    return (float(value), by_leaf(grads), by_leaf(no_aux), ref_value,
            ref_grads, fam)


def test_loss_and_every_leafs_gradient_against_the_plain_reference(
        against_reference):
    value, grads, _, ref_value, ref_grads, _ = against_reference
    assert value == pytest.approx(ref_value, rel=1e-5)
    assert set(grads) == set(ref_grads)
    for name, want in ref_grads.items():
        assert float(jnp.abs(want).max()) > 0, name
        assert close(grads[name], want, 1e-4), name


def test_the_indexer_learns_from_its_loss_alone(against_reference):
    _, grads, no_aux, _, _, fam = against_reference
    for name in grads:
        indexer = name.split(".")[-1] in fam.INDEXER_LEAVES
        if indexer:     # without the term, top-k hands it nothing
            assert float(jnp.abs(no_aux[name]).max()) == 0.0, name
            assert float(jnp.abs(grads[name]).max()) > 0.0, name
        else:           # and the term reaches no other leaf
            assert close(grads[name], no_aux[name], 1e-6), name


def test_a_registry_source_is_asked_when_the_registry_is_exported():
    import weakref
    from paddle_tpu.utils.telemetry import MetricsRegistry

    class Counts:
        n = 0

        def stats(self):
            self.n += 1
            if self.n == 3:
                raise RuntimeError("the device is gone")
            return {"rows": 10 * self.n, "share": 0.5, "skipped": None}

    reg, src = MetricsRegistry(), Counts()
    reg.add_source("sub", weakref.WeakMethod(src.stats))
    assert reg.value("sub.rows") is None        # a read asks nobody
    assert reg.snapshot()["counters"]["sub.rows"] == 10
    assert reg.snapshot()["counters"]["sub.rows"] == 20
    assert reg.value("sub.rows") == 20 and src.n == 2
    with pytest.warns(RuntimeWarning, match="'sub' raised"):
        assert reg.snapshot()["counters"]["sub.rows"] == 20   # kept
    assert reg.value("sub.share") == 0.5
    src.n = 2                   # it raises again: no second warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert reg.snapshot()["counters"]["sub.rows"] == 20
    del src                     # freed: what it last published stays
    assert reg.snapshot()["counters"]["sub.rows"] == 20
    assert reg._sources == []
