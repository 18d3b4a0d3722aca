"""Operations one trained token REQUIRES in a Llama-shaped decoder: forward +
backward of every matmul (6 x matmul parameters: the layers' projections and
MLP and the head; the embedding lookup is no matmul) plus causal attention
(QK^T and PV over on average T/2 keys, forward + backward = 3 x forward).
Recomputation is not counted."""


def matmul_params(cfg: dict, layers: int) -> int:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return layers * (h * q + 2 * h * kv + q * h + 3 * h * f) + h * cfg["vocab_size"]


def flops_per_token(cfg: dict, layers: int, seq: int) -> float:
    attn_fwd = 2 * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * (seq / 2)
    return 6.0 * matmul_params(cfg, layers) + 3.0 * attn_fwd * layers
