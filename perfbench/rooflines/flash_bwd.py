"""Flash attention backward (the dq and the dk/dv kernels together), one
layer: the four matmuls the gradient requires (dV = P^T dO, dP = dO V^T,
dQ = dS K, dK = dS^T Q), causal.  The recomputation of the scores inside the
kernels is not counted, so the share reads low rather than high."""

from perfbench.rooflines import flash_fwd


def flops(batch: int, heads: int, seq: int, head_dim: int) -> float:
    return 2.0 * flash_fwd.flops(batch, heads, seq, head_dim)


def least_seconds(peaks: dict, batch, heads, kv_heads, seq, head_dim) -> float:
    return max(flops(batch, heads, seq, head_dim) / peaks["bf16_flops_per_s"],
               2 * flash_fwd.bytes_moved(batch, heads, kv_heads, seq, head_dim)
               / peaks["hbm_bytes_per_s"])
