"""The Laguna language-model family (Laguna-S-2.1): a decoder whose
window and full layers have different numbers of query heads. Layer
``i`` takes its attention from ``layer_types[i]`` (``sliding_attention``:
the last ``sliding_window`` keys, the query's own counted;
``full_attention``: every earlier key) with
``num_attention_heads_per_layer[i]`` query heads of ``head_dim`` over
``num_key_value_heads`` key heads, no biases, no head norms, a sigmoid
gate on each head's output (``g = sigmoid(RMSNorm(x) W_g)``), and the
rotary embedding ``rope_parameters[layer_types[i]]`` names (``yarn``
over half of each head on the full layers, ``default`` over all of it
on the window layers); its feed-forward from ``mlp_layer_types[i]``
(``dense``: a SwiGLU MLP of ``intermediate_size``; ``sparse``: softmax
routing over all the experts, the top ``num_experts_per_tok``
renormalised and scaled by ``moe_routed_scaling_factor``, SwiGLU experts
of ``moe_intermediate_size`` of which this chip holds a share, and one
shared SwiGLU expert of ``shared_expert_intermediate_size`` beside
them). An untied head. Trainable as the program's ``LagunaForCausalLM``;
the family does not serve yet. (The module is ``lm_laguna``: a name that
sorts after ``llama``, which ``tests/benchmark/test_manifest.py``
expects first in the list of families.)

``model["num_experts"]`` is the number of experts HELD and
``model["expert_share"]`` = [index, count] says which: the router's width
is ``num_experts * count``.

Leaves: ``embed`` [vocab, hidden], ``norm``, ``head`` [hidden, vocab] and
``layers.{i}.`` ``ln1``, ``ln2``, ``wq``, ``wk``, ``wv``, ``wo``, ``wg``
(the gate, [hidden, heads]), then ``w1``, ``w3``, ``w2`` (the dense MLP:
``w2(silu(w1 x) * w3 x)``) or ``wr`` (router), ``eg``, ``eu`` [held,
hidden, width], ``ed`` [held, width, hidden] and ``sg``, ``su``, ``sd``
(the shared expert, as the dense MLP). Matrices are stored [in, out];
gains are ones.
"""
from ..costs import causal_pairs

REFERENCE = "laguna_ref"

_NAMES = {
    "ln1": "input_layernorm.weight", "ln2": "post_attention_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "wg": "self_attn.g_proj.weight",
    "w1": "mlp.gate_proj.weight", "w3": "mlp.up_proj.weight",
    "w2": "mlp.down_proj.weight",
    "wr": "mlp.gate_weight", "eg": "mlp.w_gate", "eu": "mlp.w_up",
    "ed": "mlp.w_down",
    "sg": "shared_expert.gate_proj.weight",
    "su": "shared_expert.up_proj.weight",
    "sd": "shared_expert.down_proj.weight"}

# what the program's decoder has, and the keys of the config that say so
_FIXED = {"attention_bias": False, "tie_word_embeddings": False,
          "gating": "per-head", "decoder_sparse_step": 1,
          "moe_apply_router_weight_on_input": False,
          "moe_router_logit_softcapping": 0}


def router_width(model: dict) -> int:
    return model["num_experts"] * model["expert_share"][1]


def heads(model: dict, i: int) -> int:
    return model["num_attention_heads_per_layer"][i]


def is_window(model: dict, i: int) -> bool:
    return model["layer_types"][i] == "sliding_attention"


def is_dense(model: dict, i: int) -> bool:
    return model["mlp_layer_types"][i] == "dense"


def layer_shapes(model: dict, i: int) -> dict:
    """{leaf: shape} of layer ``i``."""
    h, d = model["hidden_size"], model["head_dim"]
    q, kv = heads(model, i) * d, model["num_key_value_heads"] * d
    out = {"ln1": (h,), "ln2": (h,), "wq": (h, q), "wk": (h, kv),
           "wv": (h, kv), "wo": (q, h), "wg": (h, heads(model, i))}
    if is_dense(model, i):
        it = model["intermediate_size"]
        out.update(w1=(h, it), w3=(h, it), w2=(it, h))
    else:
        held, width = model["num_experts"], model["moe_intermediate_size"]
        sw = model["shared_expert_intermediate_size"]
        out.update(wr=(h, router_width(model)), eg=(held, h, width),
                   eu=(held, h, width), ed=(held, width, h),
                   sg=(h, sw), su=(h, sw), sd=(sw, h))
    return out


def leaf_shapes(model: dict):
    h, v = model["hidden_size"], model["vocab_size"]
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        if len(model[key]) != model["num_hidden_layers"]:
            raise ValueError(f"{key} does not name every layer")
    out = [("embed", (v, h))]
    for i in range(model["num_hidden_layers"]):
        for k, shape in layer_shapes(model, i).items():
            out.append((f"layers.{i}.{k}", shape,
                        "ones" if len(shape) == 1 else "normal"))
    return out + [("norm", (h,)), ("head", (h, v))]


# -- the program's model ------------------------------------------------------

def laguna_config(cfg: dict, **extra):
    from paddle_tpu.models import LagunaConfig
    m = cfg["model"]
    for key, want in _FIXED.items():
        if m[key] != want:
            raise ValueError(f"the program's decoder has {key} {want!r}; "
                             f"the configuration says {m[key]!r}")
    if set(m["gating_types"]) != {"per_head"}:
        raise ValueError("the program's gate is per head on every layer")
    dense = [i for i in range(m["num_hidden_layers"]) if is_dense(m, i)]
    if dense != [i for i in m["mlp_only_layers"]
                 if i < m["num_hidden_layers"]]:
        raise ValueError("mlp_layer_types and mlp_only_layers disagree")
    return LagunaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        num_attention_heads_per_layer=tuple(
            m["num_attention_heads_per_layer"]),
        layer_types=tuple(m["layer_types"]),
        mlp_layer_types=tuple(m["mlp_layer_types"]),
        sliding_window=m["sliding_window"],
        rope_parameters=m["rope_parameters"],
        num_experts=router_width(m),
        num_experts_per_tok=m["num_experts_per_tok"],
        moe_intermediate_size=m["moe_intermediate_size"],
        shared_expert_intermediate_size=m[
            "shared_expert_intermediate_size"],
        norm_topk_prob=m["norm_topk_prob"],
        moe_routed_scaling_factor=float(m["moe_routed_scaling_factor"]),
        expert_share=tuple(m["expert_share"]),
        rms_norm_eps=m["rms_norm_eps"], dtype=m["torch_dtype"], **extra)


def train_param_name(leaf: str) -> str:
    """The benchmark's leaf name -> LagunaForCausalLM's parameter name."""
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "norm":
        return "model.norm.weight"
    if leaf == "head":
        return "lm_head.weight"
    _, i, k = leaf.split(".")
    return f"model.layers.{i}.{_NAMES[k]}"


def build_trainable(cfg: dict):
    from paddle_tpu.models import LagunaForCausalLM
    model = LagunaForCausalLM(laguna_config(cfg, **cfg["trainer"]))
    return model, {name: train_param_name(name)
                   for name, *_ in leaf_shapes(cfg["model"])}


# -- work counts --------------------------------------------------------------

def band_pairs(seq: int, window: int) -> int:
    """(query, key) pairs of one causal sequence of ``seq`` tokens whose
    queries see the last ``window`` keys, their own counted."""
    if seq <= window:
        return causal_pairs(seq)
    return causal_pairs(window) + (seq - window) * window


def expert_layers(model: dict) -> int:
    return sum(not is_dense(model, i)
               for i in range(model["num_hidden_layers"]))


def held_rows_even(model: dict, tokens: int) -> float:
    """Rows one expert layer's held experts compute for ``tokens`` tokens
    under even routing: a token sends ``num_experts_per_tok`` rows out,
    of which the share held (held / router width) arrives here."""
    return tokens * model["num_experts_per_tok"] * model["num_experts"] \
        / router_width(model)


def token_matmul_params(model: dict) -> float:
    """Parameters a token multiplies, all layers and the head: attention's
    four projections and its gate, the dense MLP's three matrices or the
    router over its whole width, the shared expert and, of the routed
    experts, the EXPECTED rows here (``held_rows_even``)."""
    total = model["hidden_size"] * model["vocab_size"]
    for i in range(model["num_hidden_layers"]):
        shapes = layer_shapes(model, i)
        size = lambda k: shapes[k][-2] * shapes[k][-1]
        total += sum(size(k) for k in ("wq", "wk", "wv", "wo", "wg"))
        if is_dense(model, i):
            total += size("w1") + size("w3") + size("w2")
        else:
            total += size("wr") + size("sg") + size("su") + size("sd") \
                + held_rows_even(model, 1) * (
                    size("eg") + size("eu") + size("ed"))
    return total


def pair_flops(model: dict, i: int) -> int:
    """QK^T and PV of one (query, key) pair over every head of layer
    ``i``."""
    return 4 * model["head_dim"] * heads(model, i)


def attention_flops(model: dict, pairs: int, window_pairs: int,
                    window: bool = None) -> int:
    """QK^T and PV of the layers whose queries meet ``pairs`` keys (full)
    or ``window_pairs`` (under the window); ``window`` True or False
    counts those layers alone."""
    return sum(pair_flops(model, i) * int(window_pairs if is_window(
        model, i) else pairs) for i in range(model["num_hidden_layers"])
        if window is None or is_window(model, i) == window)


def forward_flops(model: dict, tokens: int, pairs: int,
                  window_pairs: int) -> int:
    """One forward pass over ``tokens`` tokens whose queries meet
    ``pairs`` keys in a full layer and ``window_pairs`` in a window
    layer: the band's pairs are counted where the band is, not the
    causal triangle."""
    return int(2 * token_matmul_params(model) * int(tokens)) \
        + attention_flops(model, pairs, window_pairs)


def train_flops(model: dict, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward) of one step."""
    return 3 * forward_flops(
        model, batch * seq, batch * causal_pairs(seq),
        batch * band_pairs(seq, model["sliding_window"]))


def _flash_flops(model: dict, work: dict, window: bool) -> int:
    if "steps" not in work:
        return 0
    b, s = work["batch"], work["seq"]
    return work["steps"] * 3 * attention_flops(
        model, b * causal_pairs(s), b * band_pairs(s, model[
            "sliding_window"]), window)


def _global_flash_flops(model: dict, work: dict) -> int:
    """What ``flash_fwd`` / ``flash_bwd_dq`` / ``flash_bwd_dkv`` have to
    do in the traced steps: the full layers' causal attention forward
    and backward, each at its own number of heads."""
    return _flash_flops(model, work, window=False)


def _window_flash_flops(model: dict, work: dict) -> int:
    """What ``flash_win_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` have to do in
    the traced steps: the window layers' attention forward and backward
    over the band's pairs, each at its own number of heads."""
    return _flash_flops(model, work, window=True)


def _expert_mm_flops(model: dict, work: dict) -> int:
    """What the routed experts' grouped matmuls have to do in the traced
    steps under even routing: per expert layer the held rows through
    three products forward and six backward (each matrix's input gradient
    and weight gradient) of 2 x hidden x width FLOPs a row. The program's
    vjp makes the forward products again, which is recomputation and
    counts nothing; the shared expert is plain matmuls and no part of
    it."""
    if "steps" not in work:
        return 0
    rows = held_rows_even(model, work["batch"] * work["seq"])
    return int(work["steps"] * expert_layers(model) * rows * 9 * 2
               * model["hidden_size"] * model["moe_intermediate_size"])


KERNEL_WORK = {"global_flash_flops": _global_flash_flops,
               "window_flash_flops": _window_flash_flops,
               "expert_mm_flops": _expert_mm_flops}
