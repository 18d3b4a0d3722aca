"""Flash attention forward, one call: causal, so half of the T x T score
matrix.  Compute-bound at these shapes (T 4096, D 128): the least time is the
operations over the bf16 peak."""


def flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    return 2 * 2 * batch * heads * seq * seq * head_dim / 2


def bytes_moved(batch: int, heads: int, kv_heads: int, seq: int, head_dim: int, itemsize=2) -> float:
    return itemsize * batch * seq * head_dim * (2 * heads + 2 * kv_heads)


def least_seconds(peaks: dict, batch, heads, kv_heads, seq, head_dim) -> float:
    return max(flops(batch, heads, seq, head_dim) / peaks["bf16_flops_per_s"],
               bytes_moved(batch, heads, kv_heads, seq, head_dim) / peaks["hbm_bytes_per_s"])
