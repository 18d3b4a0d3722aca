"""Causal attention of one query a slot over paged keys at decode, summed
over the slots and the layers that attend so: K and V (``kv_heads x head_dim``
each) of every VISIBLE token are read once, q is read and o written per
query.  One query a sequence: memory-bound, the least time is the bytes over
the HBM bandwidth; the operations (4 x heads x head_dim a visible key) are
given for the trace's sake.  (A program that gathers whole blocks of pages up
to the LONGEST live context for every slot and masks - as the XLA walk of
``ops/sparse_attention.paged_masked_attention`` does - reads several times the
K/V counted here, and shows it as a low share.)"""


def bytes_moved(visible: float, queries: float, heads: int, kv_heads: int, head_dim: int,
                itemsize: int = 2) -> float:
    return 2 * visible * kv_heads * head_dim * itemsize + 2 * queries * heads * head_dim * itemsize


def operations(visible: float, heads: int, head_dim: int) -> float:
    return 4 * visible * heads * head_dim


def least_seconds(peaks: dict, visible, queries, heads, kv_heads, head_dim) -> float:
    return max(bytes_moved(visible, queries, heads, kv_heads, head_dim) / peaks["hbm_bytes_per_s"],
               operations(visible, heads, head_dim) / peaks["bf16_flops_per_s"])
