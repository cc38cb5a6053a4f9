"""A second family, kept entirely as files (a test fixture, not a
benchmark configuration): the GPT-2 equations (learned positions,
LayerNorm with biases, one fused QKV matrix with a bias, GELU MLP with
biases, tied head), trainable as the program's ``GPTForCausalLM``.

Leaves: ``wte`` [vocab, hidden], ``wpe`` [positions, hidden], ``lnf.g``,
``lnf.b`` and ``h.{i}.{ln1.g,ln1.b,qkv.w,qkv.b,proj.w,proj.b,ln2.g,ln2.b,
fc.w,fc.b,out.w,out.b}``, matrices stored [in, out]. Gains are ones,
biases zeros, matrices normal.
"""
from ..costs import causal_pairs

REFERENCE = "gpt_ref"

_PARAMS = {"ln1.g": "ln_1.weight", "ln1.b": "ln_1.bias",
           "qkv.w": "attn.qkv_proj.weight", "qkv.b": "attn.qkv_proj.bias",
           "proj.w": "attn.out_proj.weight", "proj.b": "attn.out_proj.bias",
           "ln2.g": "ln_2.weight", "ln2.b": "ln_2.bias",
           "fc.w": "mlp.fc_in.weight", "fc.b": "mlp.fc_in.bias",
           "out.w": "mlp.fc_out.weight", "out.b": "mlp.fc_out.bias"}


def leaf_shapes(model: dict):
    h, it = model["hidden_size"], model["intermediate_size"]
    shapes = {"ln1.g": (h,), "ln1.b": (h,), "qkv.w": (h, 3 * h),
              "qkv.b": (3 * h,), "proj.w": (h, h), "proj.b": (h,),
              "ln2.g": (h,), "ln2.b": (h,), "fc.w": (h, it), "fc.b": (it,),
              "out.w": (it, h), "out.b": (h,)}
    out = [("wte", (model["vocab_size"], h)),
           ("wpe", (model["max_position_embeddings"], h))]
    for i in range(model["num_hidden_layers"]):
        for k, shape in shapes.items():
            kind = "normal" if k.endswith(".w") else \
                "ones" if k.endswith(".g") else "zeros"
            out.append((f"h.{i}.{k}", shape, kind))
    return out + [("lnf.g", (h,), "ones"), ("lnf.b", (h,), "zeros")]


def build_trainable(cfg: dict):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    m = cfg["model"]
    model = GPTForCausalLM(GPTConfig(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        intermediate_size=m["intermediate_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        max_position_embeddings=m["max_position_embeddings"],
        layer_norm_epsilon=m["layer_norm_epsilon"],
        tie_word_embeddings=m["tie_word_embeddings"],
        dtype=m["torch_dtype"], **cfg["trainer"]))
    names = {"wte": "gpt.embed_tokens.weight",
             "wpe": "gpt.embed_positions.weight",
             "lnf.g": "gpt.ln_f.weight", "lnf.b": "gpt.ln_f.bias"}
    for i in range(m["num_hidden_layers"]):
        names.update({f"h.{i}.{k}": f"gpt.layers.{i}.{p}"
                      for k, p in _PARAMS.items()})
    return model, names


# -- work counts --------------------------------------------------------------

def matmul_params(model: dict) -> int:
    """The layers' four matrices and the tied head."""
    h, it = model["hidden_size"], model["intermediate_size"]
    return model["num_hidden_layers"] * (4 * h * h + 2 * h * it) \
        + h * model["vocab_size"]


def forward_flops(model: dict, tokens: int, pairs: int) -> int:
    return 2 * matmul_params(model) * int(tokens) \
        + 4 * model["hidden_size"] * model["num_hidden_layers"] * int(pairs)


def train_flops(model: dict, batch: int, seq: int) -> int:
    return 3 * forward_flops(model, batch * seq, batch * causal_pairs(seq))


def _mlp_flops(model: dict, work: dict) -> int:
    """The two MLP matmuls of the traced training steps, forward and
    backward: a count no other family has."""
    per_token = 2 * 2 * model["hidden_size"] * model["intermediate_size"] \
        * model["num_hidden_layers"]
    return 3 * per_token * work["steps"] * work["batch"] * work["seq"]


KERNEL_WORK = {"mlp_flops": _mlp_flops}
