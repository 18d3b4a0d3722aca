"""Operations one trained token REQUIRES in a decoder whose layers are of two
kinds (``cfg["layer_types"]``: Olmo-Hybrid), counted by kind: forward +
backward of every matmul (6 x matmul parameters: each layer's projections and
MLP, and the head; the embedding lookup is no matmul), causal attention in the
FULL layers only (QK^T and PV over on average T/2 keys, forward + backward =
3 x forward), and in the LINEAR layers the gated delta rule's own recurrence
(``rooflines/linear_chunk_train.py``: 7 ``Dk x Dv`` a token and head forward,
twice that backward - not the chunked form's extra products).  The short conv
(8 operations a channel) and recomputation are not counted."""

from perfbench.rooflines import linear_chunk_train


def layer_matmul_params(cfg: dict, kind: str) -> int:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    if kind == "full_attention":
        d = h // cfg["num_attention_heads"]
        q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
        return h * q + 2 * h * kv + q * h + 3 * h * f
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    # q, k; v and the output gate; a and b; the output projection
    return 2 * h * keys + 2 * h * values + 2 * h * cfg["linear_num_value_heads"] + values * h + 3 * h * f


def matmul_params(cfg: dict, layers: int) -> int:
    return sum(layer_matmul_params(cfg, kind) for kind in cfg["layer_types"][:layers]) \
        + cfg["hidden_size"] * cfg["vocab_size"]


def flops_per_token(cfg: dict, layers: int, seq: int) -> float:
    kinds = cfg["layer_types"][:layers]
    attn_fwd = 2 * 2 * cfg["hidden_size"] * (seq / 2)          # heads x head_dim = hidden_size
    rule = linear_chunk_train.operations(1, 1, cfg["linear_num_value_heads"],
                                         cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
    return 6.0 * matmul_params(cfg, layers) + 3.0 * attn_fwd * kinds.count("full_attention") \
        + rule * kinds.count("linear_attention")
