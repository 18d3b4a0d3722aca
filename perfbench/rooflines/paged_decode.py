"""Paged decode attention, one layer's call over the active slots: every live
K and V page is read once, q is read and o written.  Memory-bound (one query
token per slot): the least time is the bytes over the HBM bandwidth."""


def bytes_moved(live_pages: float, page_size: int, kv_heads: int, heads: int, head_dim: int,
                slots: float, itemsize: int = 2) -> float:
    kv = 2 * live_pages * page_size * kv_heads * head_dim * itemsize
    return kv + 2 * slots * heads * head_dim * itemsize


def least_seconds(peaks: dict, live_pages, page_size, kv_heads, heads, head_dim, slots) -> float:
    return bytes_moved(live_pages, page_size, kv_heads, heads, head_dim, slots) \
        / peaks["hbm_bytes_per_s"]
