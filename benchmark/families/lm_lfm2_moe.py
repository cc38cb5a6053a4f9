"""The LFM2-MoE language-model family (LFM2-8B-A1B): a decoder whose
layers are of mixed kinds. Layer ``i`` takes its operator from
``layer_types[i]`` (``conv``: the gated short convolution;
``full_attention``: grouped-query attention with heads of ``hidden_size
/ num_attention_heads``, a norm over each head of q and k, rotary
embedding) and its feed-forward from ``i < num_dense_layers`` (a SwiGLU
MLP of ``intermediate_size``, else sigmoid-routed experts of
``moe_intermediate_size`` of which this chip holds a share). The
embedding is tied: there is no ``head`` leaf. Trainable as the
program's ``Lfm2MoeForCausalLM``; the family does not serve yet. (The
module is ``lm_lfm2_moe``: a name that sorts after ``llama``, which
``tests/benchmark/test_manifest.py`` expects first in the list of
families.)

``model["num_experts"]`` is the number of experts HELD and
``model["expert_share"]`` = [index, count] says which: the router's width
is ``num_experts * count``.

Leaves: ``embed`` [vocab, hidden], ``norm`` and ``layers.{i}.`` ``ln1``,
``ln2`` (the operator's and the feed-forward's norm), then by kind
``ci`` [hidden, 3 x hidden], ``cw`` [taps, hidden], ``co`` (the
convolution's in-projection, taps and out-projection) or ``wq``, ``wk``,
``wv``, ``wo``, ``qn``, ``kn`` (the heads' norms), and ``w1``, ``w3``,
``w2`` (the dense MLP: ``w2(silu(w1 x) * w3 x)``) or ``wr`` (router),
``eg``, ``eu`` [held, hidden, width], ``ed`` [held, width, hidden].
Matrices are stored [in, out]; gains are ones. The router's selection
bias is no leaf: it is a buffer that starts at zero, in the program and
in the reference alike.
"""
from ..costs import causal_pairs

REFERENCE = "lfm2_moe_ref"

_NAMES = {
    "ln1": "operator_norm.weight", "ln2": "ffn_norm.weight",
    "ci": "conv.in_proj.weight", "cw": "conv.conv_weight",
    "co": "conv.out_proj.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.out_proj.weight",
    "qn": "self_attn.q_layernorm.weight",
    "kn": "self_attn.k_layernorm.weight",
    "w1": "feed_forward.w1.weight", "w3": "feed_forward.w3.weight",
    "w2": "feed_forward.w2.weight",
    "wr": "feed_forward.gate_weight", "eg": "feed_forward.w_gate",
    "eu": "feed_forward.w_up", "ed": "feed_forward.w_down"}


def router_width(model: dict) -> int:
    return model["num_experts"] * model["expert_share"][1]


def head_dim(model: dict) -> int:
    return model["hidden_size"] // model["num_attention_heads"]


def is_attention(model: dict, i: int) -> bool:
    return model["layer_types"][i] == "full_attention"


def is_dense(model: dict, i: int) -> bool:
    return i < model["num_dense_layers"]


def layer_shapes(model: dict, i: int) -> dict:
    """{leaf: shape} of layer ``i``."""
    h, d = model["hidden_size"], head_dim(model)
    out = {"ln1": (h,), "ln2": (h,)}
    if is_attention(model, i):
        q, kv = model["num_attention_heads"] * d, \
            model["num_key_value_heads"] * d
        out.update(wq=(h, q), wk=(h, kv), wv=(h, kv), wo=(q, h),
                   qn=(d,), kn=(d,))
    else:
        out.update(ci=(h, 3 * h), cw=(model["conv_L_cache"], h), co=(h, h))
    if is_dense(model, i):
        it = model["intermediate_size"]
        out.update(w1=(h, it), w3=(h, it), w2=(it, h))
    else:
        held, width = model["num_experts"], model["moe_intermediate_size"]
        out.update(wr=(h, router_width(model)), eg=(held, h, width),
                   eu=(held, h, width), ed=(held, width, h))
    return out


def leaf_shapes(model: dict):
    h, v = model["hidden_size"], model["vocab_size"]
    if len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError("layer_types does not name every layer")
    out = [("embed", (v, h))]
    for i in range(model["num_hidden_layers"]):
        for k, shape in layer_shapes(model, i).items():
            out.append((f"layers.{i}.{k}", shape,
                        "ones" if len(shape) == 1 else "normal"))
    return out + [("norm", (h,))]


# -- the program's model ------------------------------------------------------

def lfm2_config(cfg: dict, **extra):
    from paddle_tpu.models import Lfm2MoeConfig
    m = cfg["model"]
    if m["conv_bias"]:
        raise ValueError("the program's short convolution has no bias")
    return Lfm2MoeConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        num_dense_layers=m["num_dense_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        conv_L_cache=m["conv_L_cache"], num_experts=router_width(m),
        num_experts_per_tok=m["num_experts_per_tok"],
        norm_topk_prob=m["norm_topk_prob"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        use_expert_bias=m["use_expert_bias"],
        expert_share=tuple(m["expert_share"]), norm_eps=m["norm_eps"],
        rope_theta=float(m["rope_theta"]), dtype=m["torch_dtype"], **extra)


def train_param_name(leaf: str) -> str:
    """The benchmark's leaf name -> Lfm2MoeForCausalLM's parameter name."""
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "norm":
        return "model.embedding_norm.weight"
    _, i, k = leaf.split(".")
    return f"model.layers.{i}.{_NAMES[k]}"


def build_trainable(cfg: dict):
    from paddle_tpu.models import Lfm2MoeForCausalLM
    model = Lfm2MoeForCausalLM(lfm2_config(cfg, **cfg["trainer"]))
    return model, {name: train_param_name(name)
                   for name, *_ in leaf_shapes(cfg["model"])}


# -- work counts --------------------------------------------------------------

def expert_layers(model: dict) -> int:
    return model["num_hidden_layers"] - model["num_dense_layers"]


def attention_layers(model: dict) -> int:
    return sum(is_attention(model, i)
               for i in range(model["num_hidden_layers"]))


def held_rows_even(model: dict, tokens: int) -> float:
    """Rows one expert layer's held experts compute for ``tokens`` tokens
    under even routing: a token sends ``num_experts_per_tok`` rows out,
    of which the share held (held / router width) arrives here."""
    return tokens * model["num_experts_per_tok"] * model["num_experts"] \
        / router_width(model)


def token_matmul_params(model: dict) -> float:
    """Parameters a token multiplies, all layers and the tied head: the
    convolution's two projections or the attention's four, the dense
    MLP's three matrices or the router over its whole width and, of the
    experts, the EXPECTED rows here (``held_rows_even``). The
    convolution's taps and its gates are element-wise and count
    nothing."""
    total = model["hidden_size"] * model["vocab_size"]
    for i in range(model["num_hidden_layers"]):
        shapes = layer_shapes(model, i)
        size = lambda k: shapes[k][-2] * shapes[k][-1]
        op = ("wq", "wk", "wv", "wo") if is_attention(model, i) \
            else ("ci", "co")
        total += sum(size(k) for k in op)
        if is_dense(model, i):
            total += size("w1") + size("w3") + size("w2")
        else:
            total += size("wr") + held_rows_even(model, 1) * (
                size("eg") + size("eu") + size("ed"))
    return total


def attention_flops(model: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, all heads and
    every attention layer."""
    return (4 * head_dim(model) * model["num_attention_heads"]
            * attention_layers(model) * int(pairs))


def forward_flops(model: dict, tokens: int, pairs: int) -> int:
    """One forward pass over ``tokens`` tokens whose queries meet
    ``pairs`` keys in all."""
    return int(2 * token_matmul_params(model) * int(tokens)) \
        + attention_flops(model, pairs)


def train_flops(model: dict, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward) of one step."""
    return 3 * forward_flops(model, batch * seq, batch * causal_pairs(seq))


def _flash_flops(model: dict, work: dict) -> int:
    """What ``flash_fwd`` / ``flash_bwd_dq`` / ``flash_bwd_dkv`` have to
    do in the traced steps: causal attention forward and backward."""
    if "steps" not in work:
        return 0
    return work["steps"] * 3 * attention_flops(
        model, work["batch"] * causal_pairs(work["seq"]))


def _expert_mm_flops(model: dict, work: dict) -> int:
    """What the experts' grouped matmuls have to do in the traced steps
    under even routing: per expert layer the held rows through three
    products forward and six backward (each matrix's input gradient and
    weight gradient) of 2 x hidden x width FLOPs a row. The program's vjp
    makes the forward products again, which is recomputation and counts
    nothing."""
    if "steps" not in work:
        return 0
    rows = held_rows_even(model, work["batch"] * work["seq"])
    return int(work["steps"] * expert_layers(model) * rows * 9 * 2
               * model["hidden_size"] * model["moe_intermediate_size"])


KERNEL_WORK = {"flash_flops": _flash_flops,
               "expert_mm_flops": _expert_mm_flops}
