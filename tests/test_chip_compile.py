"""The main path's Pallas kernels compile for the chip, checked without one.

The TPU compiler is installed beside jax and compiles for a DESCRIBED
v5e (``jax.experimental.topologies``), so what Mosaic refuses on the
chip it refuses here: interpret-mode tests cannot see a page slice that
is not aligned to the tiling. Shapes are Llama-3-8B's serving heads
(32 q / 8 kv, head_dim 128, bf16 pool) at block 16 and at the block
``chip_smoke.py`` uses, ``llama_mid``'s training attention, and the int4
weight-streaming matmul at the 8B MLP and vocabulary shapes. Two more
tests pin the gate that keeps the two refused pools (head_dim 64, int8
KV) off the kernels.

All of it lives in this one file: the worker that runs it loads the TPU
library and keeps it until it exits.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke

BF16 = jnp.bfloat16
SMOKE_BLOCK = chip_smoke.serve_config(tiny=False)[1]["block_size"]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_chip(monkeypatch):
    """Steer the repo's backend gates to their chip branch (they all ask
    jax.default_backend()), and keep these compiles out of the
    persistent cache: an entry compiled for a described chip cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _calls(text, stem, names):
    """Instructions of the compiled ``text`` by kernel name (alone under
    jax.grad they are %jvp_flash_fwd_.1, %transpose_jvp_flash_bwd_dq__.10,
    ...)."""
    return {name: len(re.findall(rf"{stem}{name}[_.\d]* = ", text))
            for name in names}


def _kernels_in(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(sharding, block):
    """A bf16 K or V pool at Llama-3-8B's 8 kv heads of 128."""
    return _sds(sharding, (256, 8, block, 128), BF16)


@pytest.mark.parametrize("block", sorted({16, SMOKE_BLOCK}))
def test_ragged_paged_attention_llama3_8b_heads(one_chip, on_chip, block):
    from paddle_tpu.ops.paged_attention import ragged_paged_attention
    rows = 256              # one idle-drain prefill program's width
    kv = _pool(one_chip, block)
    assert _kernels_in(
        ragged_paged_attention, _sds(one_chip, (rows, 32, 128), BF16),
        kv, kv, _sds(one_chip, (5, 8192 // block)),
        _sds(one_chip, (rows,)), _sds(one_chip, (rows,))) == 1


@pytest.mark.parametrize("block", sorted({16, SMOKE_BLOCK}))
def test_paged_attention_decode_llama3_8b_heads(one_chip, on_chip, block):
    from paddle_tpu.ops.paged_attention import paged_attention_decode
    kv = _pool(one_chip, block)
    assert _kernels_in(
        paged_attention_decode, _sds(one_chip, (8, 32, 128), BF16), kv,
        kv, _sds(one_chip, (8, 8192 // block)), _sds(one_chip, (8,))) == 1


def test_flash_attention_fwd_bwd_llama_mid(one_chip, on_chip):
    from paddle_tpu.ops.flash_attention import flash_attention
    q = _sds(one_chip, (4, 2048, 16, 128), BF16)
    kv = _sds(one_chip, (4, 2048, 8, 128), BF16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    # forward, one dq and one dk/dv call, and no partial gradient added
    # up outside them
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    assert _calls(text, "flash_", ("fwd", "bwd_dq", "bwd_dkv")) == {
        "fwd": 1, "bwd_dq": 1, "bwd_dkv": 1}
    assert "scatter" not in text


def test_flash_attention_fwd_bwd_head_dim_64(one_chip, on_chip):
    """LFM2-8B-A1B's attention at the benchmark cell's shape: 32 q / 8 kv
    heads of 64, 2 x 8192. The chip's compiler takes all three kernels at
    a head of 64 (the PAGED kernel's gate on 64 stands: below); the
    backward pass is one dq call and one dk/dv call over the 8192
    positions (ten of each, and a scatter-add of their partial sums into
    whole-sequence float32 buffers, while it walked 2048 x 2048 pairs)."""
    from paddle_tpu.ops.flash_attention import flash_attention
    q = _sds(one_chip, (2, 8192, 32, 64), BF16)
    kv = _sds(one_chip, (2, 8192, 8, 64), BF16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=64 ** -0.5) \
            .astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert _calls(text, "flash_", ("fwd", "bwd_dq", "bwd_dkv")) == {
        "fwd": 1, "bwd_dq": 1, "bwd_dkv": 1}
    assert text.count("tpu_custom_call") == 3
    assert "scatter" not in text


@pytest.mark.parametrize("window,stem", [(None, "flash"),
                                         (4096, "flash_win")])
def test_flash_attention_fwd_bwd_smallthinker_heads(one_chip, on_chip,
                                                    window, stem):
    """SmallThinker-21BA3B's attention at the benchmark cell's shape: 28 q
    / 4 kv heads of 128 (groups of seven through dk/dv's ``group``), 1 x
    16,384, without a window (layer 0) and under 4,096 keys (layers 1-3).
    The forward kernel holds K and V of all 16,384 positions, twice 8 MB
    with the pipeline's second buffer, and asks for that VMEM
    (``_vmem_room``: the default 16 MB refuses it by 0.75 MB). The
    backward pass is one dq call and one dk/dv call, each streaming the
    other side four blocks of 512 a step through the default VMEM (36
    and 21 calls of each while it walked [2048, 2048] pairs), and the
    windowed calls carry their own names."""
    from paddle_tpu.ops.flash_attention import flash_attention
    q = _sds(one_chip, (1, 16384, 28, 128), BF16)
    kv = _sds(one_chip, (1, 16384, 4, 128), BF16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window) \
            .astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert _calls(text, f"{stem}_", ("fwd", "bwd_dq", "bwd_dkv")) == {
        "fwd": 1, "bwd_dq": 1, "bwd_dkv": 1}
    assert text.count("tpu_custom_call") == 3
    assert "scatter" not in text
    other = "flash_win_fwd" if window is None else "flash_fwd"
    assert not re.findall(rf"[(_%]{other}[_.\d]* = ", text)


@pytest.mark.parametrize("k,n", [(4096, 14336), (4096, 128256)])
def test_decode_matmul_int4(one_chip, on_chip, k, n):
    from paddle_tpu.ops.pallas.decode_matmul import (decode_matmul,
                                                     decode_matmul_supported)
    x = _sds(one_chip, (8, k), BF16)
    w = (_sds(one_chip, (k // 2, n), jnp.int8),
         _sds(one_chip, (n,), jnp.float32))
    assert decode_matmul_supported(x, w)
    assert _kernels_in(decode_matmul, x, w) == 1


def test_head_and_dense_loss_at_the_training_cell_shape(one_chip, on_chip):
    """Mistral-7B's head and the models' dense causal loss at batch 4 x
    4096, forward and backward: what the end of the forward pass holds.
    The loss hands its gradient back in bf16 from the forward pass and
    shifts the labels, so the program needs the bf16 logits and their
    gradient and nothing float32 of that shape. Reading: 1.0002 logits'
    worth of temporaries (the gradient is written over the logits); the
    form before it (sliced logits, log_softmax differentiated by jax)
    read 3.0008, and its step program re-ran the head's matmul."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.functional.loss import causal_lm_loss
    b, s, d, v = 4, 4096, 4096, 32768

    def head_loss(h, w, labels):
        with paddle.no_grad():
            logits = jnp.einsum("bsd,dv->bsv", h, w)
            return causal_lm_loss(paddle.Tensor(logits),
                                  paddle.Tensor(labels))._value

    compiled = jax.jit(jax.value_and_grad(head_loss, argnums=(0, 1))).lower(
        _sds(one_chip, (b, s, d), BF16), _sds(one_chip, (d, v), BF16),
        _sds(one_chip, (b, s))).compile()
    logits_bytes = b * s * v * 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 2 * logits_bytes + (64 << 20)
    assert ".remat" not in compiled.as_text()


def _partial_sums_into(text, shapes):
    """The instructions of the compiled ``text`` that add or write partial
    sums into a float32 array of one of ``shapes`` (XLA's lowering of
    ``.at[...].add``): what a backward pass that walked pairs of calls
    left to XLA."""
    return [line for line in text.splitlines()
            if any(f"f32[{shape}]" in line for shape in shapes)
            and ("scatter" in line or "dynamic-update-slice" in line)]


def _cell_step_compiled(sharding, family, config, traffic):
    """A training cell's whole ``jit.TrainStep`` program compiled for the
    described chip: the configuration file's model through its family's
    builder, the traffic file's batch, bf16 parameters with float32
    master, m and v as ``benchmark.systems.Trainer`` holds them. Returns
    (the compiled program, its bytes by the compiler's analysis, the
    number of gradients under the step's barrier)."""
    import importlib
    import json
    import os

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.utils import telemetry

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, f"benchmark/configs/{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(repo, f"benchmark/traffic/{traffic}.json")) as f:
        mix = json.load(f)
    model, _ = importlib.import_module(
        f"benchmark.families.{family}").build_trainable(cfg)
    for p in model.parameters():      # shapes only: nothing runs here
        p._value = jax.ShapeDtypeStruct(tuple(p.shape), BF16)
    opt = optimizer.AdamW(parameters=model.parameters(), **cfg["optimizer"])
    step = paddle.jit.TrainStep(
        model, lambda out, lab: model.loss(out, lab), opt)
    opt._state = jax.eval_shape(
        opt.init_state, [p._value for p in opt._parameter_list])

    def placed(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(sharding, a.shape, a.dtype), tree)

    metrics = telemetry.default_tracer().metrics
    leaves = metrics.value("train_step.grad_barrier_leaves") or 0
    ids = _sds(sharding, (mix["batch"], mix["seq"]))
    compiled = step._build().lower(
        placed([p._value for p in step._p_tensors]),
        placed([b._value for b in step._b_tensors]), placed(opt._state),
        _sds(sharding, (), jnp.float32), _sds(sharding, (2,), jnp.uint32),
        (ids,), (ids,)).compile()
    m = compiled.memory_analysis()
    return (compiled,
            m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes,
            metrics.value("train_step.grad_barrier_leaves") - leaves)


def test_mistral_cell_whole_step_recomputes_nothing(one_chip, on_chip):
    """The training cell's whole ``jit.TrainStep`` program (Mistral-7B
    widths from the cell's configuration file, 2 layers, batch 4 x 4096):
    with every gradient finished before AdamW starts, XLA keeps no operand
    of the backward pass for the optimizer's sake, so it recomputes
    nothing (56.16 TFLOP is the step's own work; with AdamW fused into the
    weight-gradient matmuls the program ran the head's forward matmul
    twice, 60.56 TFLOP at 16.19 GB) and fits with room (14.99 GB of the
    chip's 16.9)."""
    compiled, nbytes, leaves = _cell_step_compiled(
        one_chip, "llama", "mistral_7b_v03_l2_train", "train_s4096")
    assert ".remat" not in compiled.as_text()
    assert compiled.cost_analysis()["flops"] == pytest.approx(56.16e12,
                                                              rel=0.01)
    assert nbytes < 15.5e9
    assert leaves == 21


def test_keye_cell_whole_step(one_chip, on_chip):
    """The Keye cell's whole step (4 layers, one chip's 16 of 128 experts,
    batch 2 x 8192): 18.810 TFLOP of XLA's own operations (the Pallas
    kernels, seven a layer, count for nothing there; 18.813 while the
    expert layers cut their whole 32,768-row chunk after gathering it,
    where they now gather and scatter-add blocks up to the held rows),
    12.65 GB of the chip's 16.9 (12,646,742,016 bytes; 13.18 GB,
    13,184,484,352 bytes, while lse and delta were [2, 32, 8192, 1]
    float32 arrays, whose one lane pads to 128 in HBM: 268 MB each, now
    [2, 8192, 32]; 14.11 GB while the loss's gradient with respect to
    the scores had a kernel of its own, ``indexer_loss_grad``), all 67
    leaves' gradients under the barrier, nothing recomputed. The four
    tile-walking kernels of a layer take 2 x 1,024 + 2 x 272 grid steps
    (65,536 while they took one a head and tile, above the diagonal
    too)."""
    from paddle_tpu.utils import telemetry
    metrics = telemetry.default_tracer().metrics
    folded = metrics.value("attn.sparse.target_in_backward") or 0
    walked = metrics.value("attn.sparse.grid_steps") or 0
    compiled, nbytes, leaves = _cell_step_compiled(
        one_chip, "lm_keye_vl2", "keye_vl2_30b_a3b_ep8_l4_train",
        "train_b2_s8192")
    text = compiled.as_text()
    assert ".remat" not in text
    # every layer's backward pass gathers the indexer's target inside the
    # dk / dv kernel: seven kernels a layer, none for the loss's gradient
    assert metrics.value("attn.sparse.target_in_backward") == folded + 4
    assert "%indexer_loss_grad" not in text
    assert len(re.findall(
        r"%(indexer_scores|topk_select|sparse_attn_fwd|indexer_loss_rows|"
        r"sparse_attn_bwd_dq|sparse_attn_bwd_dkv)[.\d]* = ", text)) == 28
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) == 48
    assert compiled.cost_analysis()["flops"] == pytest.approx(18.810e12,
                                                              rel=0.01)
    assert nbytes == pytest.approx(12.65e9, rel=0.02) and nbytes < 15.5e9
    assert leaves == 67
    steps = metrics.value("attn.sparse.grid_steps") - walked
    assert steps == 4 * (2 * 1024 + 2 * 272) <= 32768


def test_lfm2_cell_whole_step(one_chip, on_chip):
    """The LFM2 cell's whole step (published layers 1-5, one chip's 8 of
    32 experts, batch 2 x 8192): every layer's kind in one program, the
    flash kernels at a head of 64, 48 grouped matmuls of the four expert
    layers (three a chunk forward, nine in its vjp), all 49 leaves'
    gradients under the barrier, nothing recomputed. XLA's count takes a
    ``ragged-dot`` for its whole 32,768-row chunk and has the vjp's second
    forward pass: 26.892 TFLOP where the model's count is 21.2 (26.896
    while every chunk's gather and scatter-add went over all its rows).
    14.88 GB of the chip's 16.9 since the expert layers add their
    products into the carried sum block by block (15.13 GB while each
    chunk made a fresh [16384, 2048] array to scatter into, forward and
    in the vjp)."""
    compiled, nbytes, leaves = _cell_step_compiled(
        one_chip, "lm_lfm2_moe", "lfm2_8b_a1b_ep4_l5_train",
        "train_b2_s8192")
    text = compiled.as_text()
    assert ".remat" not in text
    calls = {name: len(re.findall(rf"%{name}[.\d]* = ", text))
             for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                          "ragged-dot-none")}
    assert calls == {"flash_fwd": 1, "flash_bwd_dq": 1,
                     "flash_bwd_dkv": 1, "ragged-dot-none": 48}
    assert not _partial_sums_into(text, ("2,32,8192,64", "2,8,8192,64"))
    assert compiled.cost_analysis()["flops"] == pytest.approx(26.892e12,
                                                              rel=0.01)
    assert nbytes == pytest.approx(14.88e9, rel=0.01) and nbytes < 15.5e9
    assert leaves == 49


def test_smallthinker_cell_whole_step(one_chip, on_chip):
    """The SmallThinker cell's whole step (published layers 0-3, one
    chip's 16 of 64 experts, batch 1 x 16,384, ``use_recompute`` on): each
    layer is a rematerialised region that keeps its flash kernel's output
    and log-sum-exp by name (``recompute(keep=FLASH_KEEP)``), so every
    forward kernel is there ONCE (under a bare ``jax.checkpoint`` it was
    there twice: 2 and 6) while the norms, projections, rotary embedding,
    router and experts are made again in the backward pass: layer 0's
    attention under the old names, layers 1-3 under ``flash_win_*``, each
    layer's backward pass one dq call and one dk/dv call (36 and 21 pairs
    of calls, their float32 partial sums added up by XLA, before the
    calls streamed the sequence); all 43 leaves' gradients under the
    barrier. 13.34 GB of the chip's 16.9 (12.90 while the backward pass
    walked pairs: the peak was then in layer 0's backward attention, and
    is now in layer 3's expert backward, where XLA holds the four layers'
    kept flash outputs; without recomputation the compiler's analysis
    reads 16.84 GB after rematerialising operations of its own
    choice)."""
    from paddle_tpu.utils import telemetry
    metrics = telemetry.default_tracer().metrics
    names = ("attn.flash.window", "attn.flash.bwd_calls",
             "recompute.regions", "recompute.regions_keeping")
    before = {name: metrics.value(name) or 0 for name in names}
    compiled, nbytes, leaves = _cell_step_compiled(
        one_chip, "lm_smallthinker", "smallthinker_21b_a3b_ep4_l4_train",
        "train_b1_s16384")
    text = compiled.as_text()
    assert ".remat" not in text
    calls = {name: len(re.findall(rf"%{name}[.\d]* = ", text))
             for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                          "flash_win_fwd", "flash_win_bwd_dq",
                          "flash_win_bwd_dkv")}
    assert calls == {"flash_fwd": 1, "flash_bwd_dq": 1, "flash_bwd_dkv": 1,
                     "flash_win_fwd": 3, "flash_win_bwd_dq": 3,
                     "flash_win_bwd_dkv": 3}
    assert not _partial_sums_into(text, ("1,28,16384,128", "1,4,16384,128"))
    assert nbytes == pytest.approx(13.34e9, rel=0.01) and nbytes < 13.5e9
    assert leaves == 43
    took = {name: metrics.value(name) - before[name] for name in names}
    # the three window layers' calls counted themselves (a layer is
    # traced once, recomputation or not), and each of the four layers is
    # a region that keeps names
    assert took["attn.flash.window"] and took["attn.flash.window"] % 3 == 0
    assert took["recompute.regions"] == took["recompute.regions_keeping"] \
        == took["attn.flash.window"] // 3 * 4
    # and each layer's backward pass made its two calls: 8 a trace
    assert took["attn.flash.bwd_calls"] == took["attn.flash.window"] // 3 * 8


def test_laguna_cell_whole_step(one_chip, on_chip):
    """The Laguna cell's whole step (published layers 0-4, one chip's 8 of
    256 experts, batch 1 x 8,192, ``use_recompute`` on with each layer's
    flash output kept): fits the chip at 15.99 GB, under the 16.0 the
    cell is held to (16.04 while the router took the experts' input a
    second time as its own ``router_input``, 16.16 with the window layers
    keeping nothing: the peak lies in layer 3's expert backward either
    way). The full layers' attention under the old names at 48 heads, the
    window layers' under ``flash_win_*`` at 72, each forward kernel ONCE and each
    layer's backward pass one dq call and one dk/dv call; 48 grouped
    matmuls of the four expert layers; all 69 leaves' gradients under the
    barrier. One trace of the step counts five gated layers, two under
    YaRN over 64 dimensions, four shared experts, three windows of 512
    and ten backward calls."""
    from paddle_tpu.utils import telemetry
    metrics = telemetry.default_tracer().metrics
    names = ("attn.gate.per_head", "rope.yarn", "moe.shared_expert",
             "attn.flash.window", "attn.flash.bwd_calls",
             "recompute.regions_keeping")
    before = {name: metrics.value(name) or 0 for name in names}
    compiled, nbytes, leaves = _cell_step_compiled(
        one_chip, "lm_laguna", "laguna_s21_ep32_l5_train", "train_b1_s8192")
    text = compiled.as_text()
    assert ".remat" not in text
    calls = {name: len(re.findall(rf"%{name}[.\d]* = ", text))
             for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                          "flash_win_fwd", "flash_win_bwd_dq",
                          "flash_win_bwd_dkv", "ragged-dot-none")}
    assert calls == {"flash_fwd": 2, "flash_bwd_dq": 2, "flash_bwd_dkv": 2,
                     "flash_win_fwd": 3, "flash_win_bwd_dq": 3,
                     "flash_win_bwd_dkv": 3, "ragged-dot-none": 48}
    assert not _partial_sums_into(text, ("1,72,8192,128", "1,48,8192,128",
                                         "1,8,8192,128"))
    assert nbytes <= 16.0e9
    assert leaves == 69
    took = {name: metrics.value(name) - before[name] for name in names}
    assert took == {"attn.gate.per_head": 5, "rope.yarn": 2,
                    "moe.shared_expert": 4, "attn.flash.window": 3,
                    "attn.flash.bwd_calls": 10,
                    "recompute.regions_keeping": 5}
    assert metrics.value("rope.rotary_dim") == 64
    assert metrics.value("attn.flash.window_size") == 512


# -- the gate: what the chip's compiler refuses never reaches it ------------

# Keye-VL-2.0's learned sparse attention at the benchmark cell's widths:
# 32 q / 4 kv heads of 128, an indexer of 16 heads of 64, 2048 of 8192 keys
KEYE = dict(b=2, h=32, hk=4, s=8192, d=128, hi=16, di=64, topk=2048)


def _keye_args(sh):
    k = KEYE
    return dict(
        q=_sds(sh, (k["b"], k["h"], k["s"], k["d"]), BF16),
        kv=_sds(sh, (k["b"], k["hk"], k["s"], k["d"]), BF16),
        qi=_sds(sh, (k["b"], k["hi"], k["s"], k["di"]), BF16),
        ki=_sds(sh, (k["b"], k["s"], k["di"]), BF16),
        w=_sds(sh, (k["b"], k["s"], k["hi"]), jnp.float32),
        scores=_sds(sh, (k["b"], k["s"], k["s"]), jnp.float32),
        mask=_sds(sh, (k["b"], k["s"], k["s"]), jnp.int8),
        lse=_sds(sh, (k["b"], k["h"], k["s"], 1), jnp.float32),
        row=_sds(sh, (k["b"], k["s"], 1), jnp.float32))


@pytest.mark.parametrize("kernel,operands,n", [
    ("indexer_scores", ("qi", "ki", "w"), 1),
    ("topk_select", ("scores",), 1),
    ("sparse_attn_fwd", ("q", "kv", "kv", "mask"), 1),
    ("indexer_loss_rows", ("q", "kv", "lse", "mask", "scores"), 1),
    # dq, and dkv with the indexer's target and the loss's gradient folded
    # in: at 512 x 512 it needs more VMEM than a kernel gets by default
    ("sparse_attn_bwd", ("q", "kv", "kv", "q", "lse", "q", "mask", "scores",
                         "row", "row"), 2),
])
def test_learned_sparse_attention_kernels_keye_widths(one_chip, on_chip,
                                                      kernel, operands, n):
    from paddle_tpu.ops.pallas import sparse_attention as sa
    from paddle_tpu.utils import telemetry
    a = _keye_args(one_chip)
    scale = KEYE["d"] ** -0.5
    fn = {"indexer_scores": sa.indexer_scores,
          "topk_select": lambda x: sa.topk_select(x, KEYE["topk"]),
          "sparse_attn_fwd": lambda *x: sa.sparse_attn_fwd(*x, scale),
          "indexer_loss_rows": lambda *x: sa.indexer_loss(*x, scale)[0],
          "sparse_attn_bwd": lambda *x: sa.sparse_attn_bwd(
              *x[:-2], x[-2:], scale)}[kernel]
    metrics = telemetry.default_tracer().metrics
    walked = metrics.value("attn.sparse.grid_steps") or 0
    text = jax.jit(fn).lower(*[a[o] for o in operands]).compile().as_text()
    assert text.count("tpu_custom_call") == n
    # a step a (row, query tile, head) for the forward and dq kernels, a
    # step a (row, causal tile) for the loss's and dk / dv: 2 x 136
    steps = {"sparse_attn_fwd": 1024, "indexer_loss_rows": 272,
             "sparse_attn_bwd": 1024 + 272}.get(kernel, 0)
    assert (metrics.value("attn.sparse.grid_steps") or 0) - walked == steps
    # the device trace tells the kernels apart by these names
    names = ("sparse_attn_bwd_dq", "sparse_attn_bwd_dkv") \
        if kernel == "sparse_attn_bwd" else (kernel,)
    for name in names:
        assert f"%{name}" in text


def test_learned_sparse_attention_whole_vjp_keye_widths(one_chip, on_chip):
    """The differentiable whole under ``jax.vjp``: seven kernels (scores,
    selection, attention, the loss's rows; scores again, dq, dkv with the
    target) and none for the loss's gradient alone."""
    from paddle_tpu.ops.pallas import sparse_attention as sa
    a = _keye_args(one_chip)
    scale = KEYE["d"] ** -0.5

    def both(q, k, v, qi, ki, w, d_out):
        (out, loss), vjp = jax.vjp(
            lambda *x: sa.learned_sparse_attention(*x, KEYE["topk"], scale),
            q, k, v, qi, ki, w)
        return loss, vjp((d_out, jnp.ones_like(loss)))

    text = jax.jit(both).lower(*[a[o] for o in (
        "q", "kv", "kv", "qi", "ki", "w", "q")]).compile().as_text()
    assert text.count("tpu_custom_call") == 7
    assert "%sparse_attn_bwd_dkv" in text
    assert "%indexer_loss_grad" not in text


def test_gate_refuses_head_dim_64(on_chip):
    from paddle_tpu.ops.paged_attention import (_pallas_decode_ok,
                                                paged_attention_impl)
    q = jax.ShapeDtypeStruct((8, 16, 64), BF16)
    pool = jax.ShapeDtypeStruct((64, 16, 16, 64), BF16)
    assert not _pallas_decode_ok(q, pool)
    assert paged_attention_impl(64, 16, False).startswith("reference")
    assert paged_attention_impl(128, 16, False) == "pallas"


def test_gate_refuses_quantized_pool(on_chip):
    from paddle_tpu.ops.paged_attention import (_pallas_decode_ok,
                                                paged_attention_impl)
    q = jax.ShapeDtypeStruct((8, 32, 128), BF16)
    pool = (jax.ShapeDtypeStruct((64, 8, 16, 128), jnp.int8),
            jax.ShapeDtypeStruct((64, 8, 16), jnp.float32))
    assert not _pallas_decode_ok(q, pool)
    assert _pallas_decode_ok(q, pool[0])
    assert paged_attention_impl(128, 16, True).startswith("reference")


def test_engine_names_the_reference_path_loudly(on_chip, caplog):
    """A pool the kernels are refused for serves through the reference
    by a logged decision: one WARNING line at engine construction
    naming the implementation of every program family."""
    import logging

    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    paddle.seed(0)
    model = LlamaForCausalLM(llama_tiny())          # head_dim 32
    with caplog.at_level(logging.INFO, logger="paddle_tpu.serving"):
        eng = ServingEngine(model, num_blocks=16, block_size=8,
                            ragged=True)
    assert eng.attention_impls["ragged"].startswith(
        "reference (head_dim 32")
    assert eng.attention_impls["decode"] == eng.attention_impls["ragged"]
    assert eng.attention_impls["prefill"] == "flash_attention"
    [rec] = [r for r in caplog.records if "attention per program" in
             r.getMessage()]
    assert rec.levelno == logging.WARNING
    assert "ragged=reference (head_dim 32" in rec.getMessage()
