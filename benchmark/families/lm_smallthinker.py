"""The SmallThinker language-model family (SmallThinker-21BA3B-Instruct):
a decoder that mixes full and window attention and routes before
attention. Layer ``i`` attends every earlier key where
``sliding_window_layout[i]`` is 0 and the last ``sliding_window_size``
keys (the query's own counted) where it is 1; its q and k take the rotary
embedding where ``rope_layout[i]`` is 1 and no position embedding where
it is 0. Grouped-query attention with its own ``head_dim``, no biases, no
head norms. The router reads the layer's INPUT (the residual stream,
before the norm and before attention): softmax over all the experts, the
``moe_num_active_primary_experts`` largest, renormalised; the experts,
``w_down(relu(w_gate u) * w_up u)`` on the normed stream after attention,
of which this chip holds a share. An untied head. Trainable as the
program's ``SmallThinkerForCausalLM``; the family does not serve yet.
(The module is ``lm_smallthinker``: a name that sorts after ``llama``,
which ``tests/benchmark/test_manifest.py`` expects first in the list of
families.)

``model["moe_num_primary_experts"]`` is the number of experts HELD and
``model["expert_share"]`` = [index, count] says which: the router's width
is ``moe_num_primary_experts * count``.

Leaves: ``embed`` [vocab, hidden], ``norm``, ``head`` [hidden, vocab] and
``layers.{i}.`` ``ln1``, ``ln2``, ``wq``, ``wk``, ``wv``, ``wo``, ``wr``
(router), ``eg``, ``eu`` [held, hidden, width], ``ed`` [held, width,
hidden]. Matrices are stored [in, out]; gains are ones.
"""
from ..costs import causal_pairs

REFERENCE = "smallthinker_ref"

_NAMES = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "wr": "block_sparse_moe.gate_weight", "eg": "block_sparse_moe.w_gate",
    "eu": "block_sparse_moe.w_up", "ed": "block_sparse_moe.w_down"}


def router_width(model: dict) -> int:
    return model["moe_num_primary_experts"] * model["expert_share"][1]


def window_of(model: dict, i: int):
    """Layer ``i``'s window in keys, or None where it sees every key."""
    return model["sliding_window_size"] \
        if model["sliding_window_layout"][i] else None


def layer_shapes(model: dict) -> dict:
    """{leaf: shape} of one layer."""
    h, d = model["hidden_size"], model["head_dim"]
    q, kv = model["num_attention_heads"] * d, \
        model["num_key_value_heads"] * d
    held, width = model["moe_num_primary_experts"], \
        model["moe_ffn_hidden_size"]
    return {"ln1": (h,), "ln2": (h,), "wq": (h, q), "wk": (h, kv),
            "wv": (h, kv), "wo": (q, h), "wr": (h, router_width(model)),
            "eg": (held, h, width), "eu": (held, h, width),
            "ed": (held, width, h)}


def leaf_shapes(model: dict):
    h, v = model["hidden_size"], model["vocab_size"]
    for key in ("sliding_window_layout", "rope_layout"):
        if len(model[key]) != model["num_hidden_layers"]:
            raise ValueError(f"{key} does not name every layer")
    out = [("embed", (v, h))]
    for i in range(model["num_hidden_layers"]):
        for k, shape in layer_shapes(model).items():
            out.append((f"layers.{i}.{k}", shape,
                        "ones" if len(shape) == 1 else "normal"))
    return out + [("norm", (h,)), ("head", (h, v))]


# -- the program's model ------------------------------------------------------

def smallthinker_config(cfg: dict, **extra):
    from paddle_tpu.models import SmallThinkerConfig
    m = cfg["model"]
    if m["tie_word_embeddings"] or m["rope_scaling"] is not None:
        raise ValueError("the program's decoder has an untied head and no "
                         "rotary scaling")
    return SmallThinkerConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        moe_ffn_hidden_size=m["moe_ffn_hidden_size"],
        moe_num_primary_experts=router_width(m),
        moe_num_active_primary_experts=m["moe_num_active_primary_experts"],
        moe_primary_router_apply_softmax=m[
            "moe_primary_router_apply_softmax"],
        norm_topk_prob=m["norm_topk_prob"],
        expert_share=tuple(m["expert_share"]),
        sliding_window_size=m["sliding_window_size"],
        sliding_window_layout=tuple(m["sliding_window_layout"]),
        rope_layout=tuple(m["rope_layout"]),
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        dtype=m["torch_dtype"], **extra)


def train_param_name(leaf: str) -> str:
    """The benchmark's leaf name -> SmallThinkerForCausalLM's parameter
    name."""
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "norm":
        return "model.norm.weight"
    if leaf == "head":
        return "lm_head.weight"
    _, i, k = leaf.split(".")
    return f"model.layers.{i}.{_NAMES[k]}"


def build_trainable(cfg: dict):
    from paddle_tpu.models import SmallThinkerForCausalLM
    model = SmallThinkerForCausalLM(
        smallthinker_config(cfg, **cfg["trainer"]))
    return model, {name: train_param_name(name)
                   for name, *_ in leaf_shapes(cfg["model"])}


# -- work counts --------------------------------------------------------------

def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one causal sequence of ``seq`` tokens whose
    queries see the last ``window`` keys, their own counted."""
    if seq <= window:
        return causal_pairs(seq)
    return causal_pairs(window) + (seq - window) * window


def window_layers(model: dict) -> int:
    return sum(model["sliding_window_layout"])


def global_layers(model: dict) -> int:
    return model["num_hidden_layers"] - window_layers(model)


def held_rows_even(model: dict, tokens: int) -> float:
    """Rows one layer's held experts compute for ``tokens`` tokens under
    even routing: a token sends ``moe_num_active_primary_experts`` rows
    out, of which the share held (held / router width) arrives here."""
    return tokens * model["moe_num_active_primary_experts"] \
        * model["moe_num_primary_experts"] / router_width(model)


def token_matmul_params(model: dict) -> float:
    """Parameters a token multiplies, all layers and the head: attention's
    four projections, the router over its whole width and, of the
    experts, the EXPECTED rows here (``held_rows_even``)."""
    shapes = layer_shapes(model)
    size = lambda k: shapes[k][-2] * shapes[k][-1]
    layer = sum(size(k) for k in ("wq", "wk", "wv", "wo", "wr")) \
        + held_rows_even(model, 1) * (size("eg") + size("eu") + size("ed"))
    return model["num_hidden_layers"] * layer \
        + model["hidden_size"] * model["vocab_size"]


def pair_flops(model: dict) -> int:
    """QK^T and PV of one (query, key) pair over every head of a layer."""
    return 4 * model["head_dim"] * model["num_attention_heads"]


def forward_flops(model: dict, tokens: int, pairs: int,
                  window_pairs: int) -> int:
    """One forward pass over ``tokens`` tokens whose queries meet
    ``pairs`` keys in a global layer and ``window_pairs`` in a window
    layer: the band's pairs are counted where the band is, not the
    causal triangle."""
    return int(2 * token_matmul_params(model) * int(tokens)) \
        + pair_flops(model) * (global_layers(model) * int(pairs)
                               + window_layers(model) * int(window_pairs))


def train_flops(model: dict, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward) of one step."""
    return 3 * forward_flops(
        model, batch * seq, batch * causal_pairs(seq),
        batch * band_pairs(seq, model["sliding_window_size"]))


def _global_flash_flops(model: dict, work: dict) -> int:
    """What ``flash_fwd`` / ``flash_bwd_dq`` / ``flash_bwd_dkv`` have to
    do in the traced steps: the global layers' causal attention forward
    and backward."""
    if "steps" not in work:
        return 0
    return work["steps"] * 3 * pair_flops(model) * global_layers(model) \
        * work["batch"] * causal_pairs(work["seq"])


def _window_flash_flops(model: dict, work: dict) -> int:
    """What ``flash_win_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` have to do in
    the traced steps: the window layers' attention forward and backward
    over the band's pairs."""
    if "steps" not in work:
        return 0
    return work["steps"] * 3 * pair_flops(model) * window_layers(model) \
        * work["batch"] * band_pairs(work["seq"],
                                     model["sliding_window_size"])


def _expert_mm_flops(model: dict, work: dict) -> int:
    """What the experts' grouped matmuls have to do in the traced steps
    under even routing: per layer the held rows through three products
    forward and six backward (each matrix's input gradient and weight
    gradient) of 2 x hidden x width FLOPs a row. The program's vjp makes
    the forward products again, which is recomputation and counts
    nothing."""
    if "steps" not in work:
        return 0
    rows = held_rows_even(model, work["batch"] * work["seq"])
    return int(work["steps"] * model["num_hidden_layers"] * rows * 9 * 2
               * model["hidden_size"] * model["moe_ffn_hidden_size"])


KERNEL_WORK = {"global_flash_flops": _global_flash_flops,
               "window_flash_flops": _window_flash_flops,
               "expert_mm_flops": _expert_mm_flops}
