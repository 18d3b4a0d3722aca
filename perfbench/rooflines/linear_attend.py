"""The gated delta rule's two forms (``accelerate_tpu/ops/gated_delta.py``),
summed over the live slot-layers.

**One token a slot** (a decode step; ``bytes_moved`` / ``operations`` /
``least_seconds``).  A value head's state ``[Dk, Dv]`` float32 is read once
and written once; beside it the step's own rows: q and k ``[Dk]``, v
``[Dv]``, g and beta, in float32, and o ``[Dv]`` out.  Operations: the four
``Dk x Dv`` products a head (the decay, ``S^T k``, the rank-one write, ``S^T
q``), a multiply and an add each where they contract.  At 128 x 128 that is
131 kFLOP against 131 KB a head, 1 FLOP a byte under the chip's 240:
memory-bound, the least time is the bytes over the HBM bandwidth.

**A chunk of ``tokens`` positions** (a prefill chunk; ``chunk_*``; no
benchmark reader, for the reason ``traffic/serve_assist.json`` gives: the
builder's own longer trace reads it into ``PERF.md``).  Blocks of ``C``
tokens: a head's state is read and written once a CHUNK, q, k, v, g, beta read
and o written a token.  Operations a block and head: ``K K^T`` and ``Q K^T``
(``C^2 Dk`` each), ``T = (I - A)^-1`` as ten ``C^3`` products, ``T [V, K]``
(``C^2 (Dv + Dk)``), ``W S`` and ``Q S`` (``C Dk Dv`` each), ``(Q K^T) V'``
(``C^2 Dv``) and ``K^T V'`` (``C Dk Dv``); two operations a multiply-add."""


def bytes_moved(slot_layers: float, heads: int, dk: int, dv: int) -> float:
    state = 2 * heads * dk * dv * 4
    rows = heads * (2 * dk + 2 * dv + 2) * 4
    return slot_layers * (state + rows)


def operations(slot_layers: float, heads: int, dk: int, dv: int) -> float:
    return slot_layers * heads * (dk * dv + 3 * 2 * dk * dv)


def least_seconds(peaks: dict, slot_layers, heads, dk, dv) -> float:
    return max(bytes_moved(slot_layers, heads, dk, dv) / peaks["hbm_bytes_per_s"],
               operations(slot_layers, heads, dk, dv) / peaks["bf16_flops_per_s"])


def chunk_bytes_moved(layers: float, tokens: int, heads: int, dk: int, dv: int) -> float:
    state = 2 * heads * dk * dv * 4
    rows = tokens * heads * (2 * dk + 2 * dv + 2) * 4
    return layers * (state + rows)


def chunk_operations(layers: float, tokens: int, heads: int, dk: int, dv: int, block: int = 64) -> float:
    blocks = -(-tokens // block)
    a_block = 2 * block * block * dk + 10 * block ** 3 + block * block * (dv + dk) \
        + 3 * block * dk * dv + block * block * dv
    return layers * blocks * heads * 2 * a_block


def chunk_least_seconds(peaks: dict, layers, tokens, heads, dk, dv) -> float:
    return max(chunk_bytes_moved(layers, tokens, heads, dk, dv) / peaks["hbm_bytes_per_s"],
               chunk_operations(layers, tokens, heads, dk, dv) / peaks["bf16_flops_per_s"])
