"""The Keye-VL-2.0 language-model family: RMSNorm, grouped-query
attention with its own ``head_dim`` and a norm over each head of q and k,
rotary embedding, *learned sparse attention* (an indexer scores the
causal keys, each query attends its ``sa_config.topk`` best) with the
indexer's own loss, and in every layer a gated-expert layer routed over
all the experts of which this chip holds a share. Trainable as the
program's ``KeyeVL2ForCausalLM``; the family does not serve yet. (The
module is ``lm_keye_vl2``, the language model of Keye-VL-2.0: a name that
sorts after ``llama``, which ``tests/benchmark/test_manifest.py`` expects
first in the list of families.)

``model["num_experts"]`` is the number of experts HELD and
``model["expert_share"]`` = [index, count] says which: the router's width
is ``num_experts * count``.

Leaves: ``embed`` [vocab, hidden], ``norm``, ``head`` [hidden, vocab] and
``layers.{i}.`` ``ln1``, ``ln2``, ``wq``, ``wk``, ``wv``, ``wo``, ``qn``,
``kn`` (the heads' norms), ``iwq``, ``iwk``, ``iww``, ``ikn`` (the
indexer's three matrices and its key norm), ``wr`` (router), ``eg``,
``eu`` [held, hidden, width], ``ed`` [held, width, hidden]. Matrices are
stored [in, out]; gains are ones.
"""
from ..costs import causal_pairs

REFERENCE = "keye_vl2_ref"

_TRAIN_NAMES = {
    "ln1": "input_layernorm", "ln2": "post_attention_layernorm",
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
    "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
    "qn": "self_attn.q_norm", "kn": "self_attn.k_norm",
    "iwq": "self_attn.indexer_q_proj", "iwk": "self_attn.indexer_k_proj",
    "iww": "self_attn.indexer_weights_proj",
    "ikn": "self_attn.indexer_k_norm"}
_MOE_NAMES = {"wr": "mlp.gate_weight", "eg": "mlp.w_gate",
              "eu": "mlp.w_up", "ed": "mlp.w_down"}
INDEXER_LEAVES = ("iwq", "iwk", "iww", "ikn")


def router_width(model: dict) -> int:
    return model["num_experts"] * model["expert_share"][1]


def layer_shapes(model: dict) -> dict:
    """{leaf: shape} of one layer."""
    h, d = model["hidden_size"], model["head_dim"]
    q, kv = model["num_attention_heads"] * d, \
        model["num_key_value_heads"] * d
    sa = model["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    held, width = model["num_experts"], model["moe_intermediate_size"]
    return {"ln1": (h,), "ln2": (h,), "wq": (h, q), "wk": (h, kv),
            "wv": (h, kv), "wo": (q, h), "qn": (d,), "kn": (d,),
            "iwq": (h, hi * di), "iwk": (h, di), "iww": (h, hi),
            "ikn": (di,), "wr": (h, router_width(model)),
            "eg": (held, h, width), "eu": (held, h, width),
            "ed": (held, width, h)}


def leaf_shapes(model: dict):
    h, v = model["hidden_size"], model["vocab_size"]
    out = [("embed", (v, h))]
    for i in range(model["num_hidden_layers"]):
        for k, shape in layer_shapes(model).items():
            out.append((f"layers.{i}.{k}", shape,
                        "ones" if len(shape) == 1 else "normal"))
    return out + [("norm", (h,)), ("head", (h, v))]


# -- the program's model ------------------------------------------------------

def keye_config(cfg: dict, **extra):
    from paddle_tpu.models import KeyeVL2Config
    m = cfg["model"]
    sa = m["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program's indexer has one key head")
    if m["mlp_only_layers"] or m["decoder_sparse_step"] != 1:
        raise ValueError("the program's decoder has an expert layer in "
                         "every layer and no dense MLP")
    if m.get("sliding_window") is not None or m["attention_bias"]:
        raise ValueError("the program's attention has no window and no bias")
    return KeyeVL2Config(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_experts=router_width(m),
        num_experts_per_tok=m["num_experts_per_tok"],
        norm_topk_prob=m["norm_topk_prob"],
        expert_share=tuple(m["expert_share"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        dtype=m["torch_dtype"], **extra)


def train_param_name(leaf: str) -> str:
    """The benchmark's leaf name -> KeyeVL2ForCausalLM's parameter name."""
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "norm":
        return "model.norm.weight"
    if leaf == "head":
        return "lm_head.weight"
    _, i, k = leaf.split(".")
    if k in _MOE_NAMES:
        return f"model.layers.{i}.{_MOE_NAMES[k]}"
    return f"model.layers.{i}.{_TRAIN_NAMES[k]}.weight"


def build_trainable(cfg: dict):
    from paddle_tpu.models import KeyeVL2ForCausalLM
    model = KeyeVL2ForCausalLM(keye_config(cfg, **cfg["trainer"]))
    return model, {name: train_param_name(name)
                   for name, *_ in leaf_shapes(cfg["model"])}


# -- work counts --------------------------------------------------------------

def selected_pairs(model: dict, seq: int) -> int:
    """(query, key) pairs one sequence's selection holds: query t keeps
    min(t + 1, topk) keys."""
    t = min(seq, model["sa_config"]["topk"])
    return t * (t + 1) // 2 + (seq - t) * model["sa_config"]["topk"]


def token_matmul_params(model: dict) -> float:
    """Parameters a token multiplies, all layers and the head: the
    attention's four matrices, the indexer's three, the router over its
    whole width and, of the experts, the EXPECTED rows here: a token
    sends ``num_experts_per_tok`` rows out, of which the share held
    (held / router width) arrives under even routing."""
    shapes = layer_shapes(model)
    size = lambda k: shapes[k][-2] * shapes[k][-1]
    dense = sum(size(k) for k in ("wq", "wk", "wv", "wo", "iwq", "iwk",
                                  "iww", "wr"))
    rows = model["num_experts_per_tok"] * model["num_experts"] \
        / router_width(model)
    expert = rows * (size("eg") + size("eu") + size("ed"))
    return model["num_hidden_layers"] * (dense + expert) \
        + model["hidden_size"] * model["vocab_size"]


def attention_flops(model: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` selected pairs, all heads and layers."""
    return (4 * model["head_dim"] * model["num_attention_heads"]
            * model["num_hidden_layers"] * int(pairs))


def indexer_flops(model: dict, pairs: int) -> int:
    """The indexer's qI . kI over ``pairs`` causal pairs, all its heads
    and all layers (the relu and the heads' weighted sum count nothing)."""
    sa = model["sa_config"]
    return (2 * sa["indexer_head_dim"] * sa["indexer_num_heads"]
            * model["num_hidden_layers"] * int(pairs))


def forward_flops(model: dict, tokens: int, pairs: int,
                  causal: int = None) -> int:
    """One forward pass over ``tokens`` tokens whose queries attend
    ``pairs`` selected keys out of ``causal`` scored ones. The selection
    (compares and counts) and the indexer's loss over the probabilities
    the attention already made are no matmul and count nothing."""
    causal = pairs if causal is None else causal
    return int(2 * token_matmul_params(model) * int(tokens)) \
        + attention_flops(model, pairs) + indexer_flops(model, causal)


def train_flops(model: dict, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward) of one step."""
    return 3 * forward_flops(model, batch * seq,
                             batch * selected_pairs(model, seq),
                             batch * causal_pairs(seq))


def _sparse_attn_flops(model: dict, work: dict) -> int:
    """What ``sparse_attn_fwd`` / ``_bwd_dq`` / ``_bwd_dkv`` have to do
    in the traced steps: attention over the selected pairs, forward and
    backward."""
    if "steps" not in work:
        return 0
    return work["steps"] * 3 * attention_flops(
        model, work["batch"] * selected_pairs(model, work["seq"]))


def _indexer_scores_flops(model: dict, work: dict) -> int:
    """What the ``indexer_scores`` kernel has to do in the traced steps:
    the scores over the causal pairs ONCE a step. The program calls it
    twice (it makes the scores again in the backward pass), which is
    recomputation and counts nothing; the scores' backward matmuls are
    XLA's, not this kernel's."""
    if "steps" not in work:
        return 0
    return work["steps"] * indexer_flops(
        model, work["batch"] * causal_pairs(work["seq"]))


KERNEL_WORK = {"sparse_attn_flops": _sparse_attn_flops,
               "indexer_scores_flops": _indexer_scores_flops}
