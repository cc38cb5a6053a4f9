"""Operations and bytes the algorithm needs, from shapes alone.

Counted for what the mathematics requires, whatever implements it, so a
share of a peak computed from these cannot pass 100% and stays valid
when a kernel is replaced. ``model`` is a configuration file's ``model``
object. Matmul FLOPs are 2 per multiply-add; the embedding is a gather
and counts nothing; causal attention is counted once (each query against
the keys at or before it); recomputation counts nothing.
"""


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


def causal_pairs(seq: int) -> int:
    """(query, key) pairs of one causal sequence of ``seq`` tokens."""
    return seq * (seq + 1) // 2


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
