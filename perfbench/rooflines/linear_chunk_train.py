"""The gated delta rule over whole sequences, forward AND backward (a train
step's ``linear_chunk`` scope), summed over the linear-attention layers:
what ANY implementation must move and compute, not what the chunked form of
``accelerate_tpu/ops/gated_delta.py`` does - no block size, no triangular
inverse, no recomputation.

Bytes a token and value head, float32 (what the rule is handed and hands
back): forward q and k ``[Dk]``, v ``[Dv]``, g and beta read and o ``[Dv]``
written; backward those five and ``do`` read and the five gradients written.
Operations: the recurrence's own four ``Dk x Dv`` products a token and head
(the decay one operation an element; ``S^T k``, the rank-one write and ``S^T
q`` a multiply and an add each: 7), and twice that for the backward pass.
At 96 x 192 that is 387 kFLOP against 6.2 KB a token and head: 63 FLOP a byte
under the chip's 240, memory-bound: the least time is the bytes over the HBM
bandwidth (1.85 ms a layer and step at 8,192 tokens and 30 heads)."""


def bytes_moved(layers: float, tokens: int, heads: int, dk: int, dv: int) -> float:
    rows = 2 * dk + dv + 2                       # q, k, v, g, beta
    forward = rows + dv                          # ... read, o written
    backward = rows + dv + rows                  # ... and do read, the five gradients written
    return layers * tokens * heads * (forward + backward) * 4


def operations(layers: float, tokens: int, heads: int, dk: int, dv: int) -> float:
    return layers * tokens * heads * 3 * 7 * dk * dv


def least_seconds(peaks: dict, layers, tokens, heads, dk, dv) -> float:
    return max(bytes_moved(layers, tokens, heads, dk, dv) / peaks["hbm_bytes_per_s"],
               operations(layers, tokens, heads, dk, dv) / peaks["bf16_flops_per_s"])
