"""The Llama family (RMSNorm, rotary embedding, grouped-query attention,
SwiGLU, no biases, untied head): Mistral-7B-v0.3's equations. Trainable
as the program's ``LlamaForCausalLM``, served by ``PagedLlamaDecoder``.

Leaf names are the serving loader's: ``embed`` [vocab, hidden], ``norm``,
``head`` [hidden, vocab], ``layers.{i}.{ln1,ln2,wq,wk,wv,wo,wg,wu,wd}``
with matrices stored [in, out]. No leaf names an init kind: gains (one
dimension) are ones, matrices normal.
"""
from ..costs import causal_pairs

REFERENCE = "llama_ref"
LAYER_MATS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")


def leaf_shapes(model: dict):
    """[(name, shape)] of every leaf, in the serving loader's order."""
    h, it, v = model["hidden_size"], model["intermediate_size"], \
        model["vocab_size"]
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd
    out = [("embed", (v, h))]
    mats = {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h),
            "wg": (h, it), "wu": (h, it), "wd": (it, h)}
    for i in range(model["num_hidden_layers"]):
        out.append((f"layers.{i}.ln1", (h,)))
        out.append((f"layers.{i}.ln2", (h,)))
        out += [(f"layers.{i}.{k}", mats[k]) for k in LAYER_MATS]
    out += [("norm", (h,)), ("head", (h, v))]
    return out


# -- the program's models -----------------------------------------------------

def llama_config(cfg: dict, **extra):
    from paddle_tpu.models import LlamaConfig
    m = cfg["model"]
    if m["hidden_size"] != m["num_attention_heads"] * m["head_dim"]:
        raise ValueError("the program derives head_dim as hidden_size / "
                         "num_attention_heads; this config disagrees")
    if m.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    return LlamaConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        max_position_embeddings=m["max_position_embeddings"],
        rms_norm_eps=m["rms_norm_eps"], rope_theta=m["rope_theta"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=m["torch_dtype"], **extra)


_TRAIN_NAMES = {"wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
                "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
                "wg": "mlp.gate_proj", "wu": "mlp.up_proj",
                "wd": "mlp.down_proj", "ln1": "input_layernorm",
                "ln2": "post_attention_layernorm"}


def train_param_name(leaf: str) -> str:
    """The benchmark's leaf name -> LlamaForCausalLM's parameter name."""
    if leaf == "embed":
        return "model.embed_tokens.weight"
    if leaf == "norm":
        return "model.norm.weight"
    if leaf == "head":
        return "lm_head.weight"
    _, i, k = leaf.split(".")
    return f"model.layers.{i}.{_TRAIN_NAMES[k]}.weight"


def build_trainable(cfg: dict):
    from paddle_tpu.models import LlamaForCausalLM
    model = LlamaForCausalLM(llama_config(cfg, **cfg["trainer"]))
    return model, {name: train_param_name(name)
                   for name, _ in leaf_shapes(cfg["model"])}


def build_decoder(cfg: dict, load, **decoder):
    from paddle_tpu.inference.paged_decode import PagedLlamaDecoder
    return PagedLlamaDecoder.from_weight_loader(
        llama_config(cfg), load, **dict(cfg["decoder"], **decoder))


# -- work counts --------------------------------------------------------------

def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    the layers' seven matrices and the output head."""
    h, it = model["hidden_size"], model["intermediate_size"]
    hd = model["head_dim"]
    q, kv = model["num_attention_heads"] * hd, \
        model["num_key_value_heads"] * hd
    per_layer = h * (q + 2 * kv) + q * h + 3 * h * it
    return model["num_hidden_layers"] * per_layer + h * model["vocab_size"]


def total_params(model: dict) -> int:
    """Every parameter: matmul parameters, the embedding (untied) and
    the norm gains."""
    h = model["hidden_size"]
    emb = 0 if model["tie_word_embeddings"] else h * model["vocab_size"]
    norms = (2 * model["num_hidden_layers"] + 1) * h
    return matmul_params(model) + emb + norms


def kv_bytes_per_token(model: dict, bytes_per_value: int = 2) -> int:
    """K and V of one token over all layers, as the pool stores them."""
    return (2 * model["num_hidden_layers"] * model["num_key_value_heads"]
            * model["head_dim"] * bytes_per_value)


def attention_flops(model: dict, pairs: int) -> int:
    """QK^T and PV over ``pairs`` (query, key) pairs, all layers and
    heads: 4 * head_dim FLOPs per pair, head and layer."""
    return (4 * model["head_dim"] * model["num_attention_heads"]
            * model["num_hidden_layers"] * int(pairs))


def forward_flops(model: dict, tokens: int, pairs: int) -> int:
    """One forward pass over ``tokens`` tokens whose queries meet
    ``pairs`` keys in all."""
    return 2 * matmul_params(model) * int(tokens) \
        + attention_flops(model, pairs)


def train_flops(model: dict, batch: int, seq: int) -> int:
    """Forward and backward (twice the forward) of one step."""
    return 3 * forward_flops(model, batch * seq, batch * causal_pairs(seq))


def flash_train_flops(model: dict, batch: int, seq: int) -> int:
    """The attention part of ``train_flops``: forward, dq and dk/dv."""
    return 3 * attention_flops(model, batch * causal_pairs(seq))


def kv_read_bytes(model: dict, pairs: int, bytes_per_value: int = 2) -> int:
    """Bytes of K and V that attention over ``pairs`` (query, key) pairs
    must read when every query reads its own keys (decode rows do; rows
    of one prefill chunk could share, so this is the least for decode
    and an upper count for prefill rows)."""
    return kv_bytes_per_token(model, bytes_per_value) * int(pairs)


def weight_stream_bytes(model: dict, bytes_per_weight: int = 1) -> int:
    """Bytes of matmul weights one step must stream from HBM."""
    return matmul_params(model) * bytes_per_weight


def _flash_flops(model: dict, work: dict) -> int:
    """The flash kernels' part of the traced training steps."""
    if "steps" not in work:
        return 0
    return work["steps"] * flash_train_flops(model, work["batch"],
                                             work["seq"])


def _decode_kv_bytes(model: dict, work: dict) -> int:
    """What the ragged kernel's decode rows of the traced window read."""
    return kv_read_bytes(model, work.get("decode_pairs", 0))


KERNEL_WORK = {"flash_flops": _flash_flops,
               "decode_kv_bytes": _decode_kv_bytes}
