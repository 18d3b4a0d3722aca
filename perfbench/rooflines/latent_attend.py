"""The absorbed walk of one decode step over latent rows, summed over the
slots and the layers: the row ``[c ; kr]`` (``kv_lora_rank + rope`` values) of
every VISIBLE token is read ONCE for all the heads held here — it is the key
(whole) and, in its first ``kv_lora_rank`` values, the value — and each query
reads its absorbed ``q`` (``heads x row``) and writes ``u`` (``heads x
kv_lora_rank``).  Operations: a visible key costs every held head a dot over
the row and a sum over its value part, ``heads x (row + kv_lora_rank) x 2``.
With 4 of 32 heads held that is 8.7 kFLOP against 1,152 B, 7.6 FLOP a byte
under the chip's 240: memory-bound, the least time is the bytes over the HBM
bandwidth (all 32 heads on one chip would be 60 FLOP a byte and still
memory-bound; 128 heads, as in DeepSeek-V3, sit at the ridge: 242).  The row counted is
its CONTENT (576 values), not the 640 lanes the pool keeps it in; a program
that gathers whole blocks of pages up to the LONGEST live context for every
slot and reads the gathered block twice — as the XLA walk of
``ops/sparse_attention.paged_masked_attention`` does — moves several times
these bytes, and shows it as a low share."""


def bytes_moved(visible: float, queries: float, heads: int, kv_lora_rank: int, rope: int,
                itemsize: int = 2) -> float:
    row = kv_lora_rank + rope
    return visible * row * itemsize + queries * heads * (row + kv_lora_rank) * itemsize


def operations(visible: float, heads: int, kv_lora_rank: int, rope: int) -> float:
    return visible * heads * (kv_lora_rank + rope + kv_lora_rank) * 2


def least_seconds(peaks: dict, visible, queries, heads, kv_lora_rank, rope) -> float:
    return max(bytes_moved(visible, queries, heads, kv_lora_rank, rope) / peaks["hbm_bytes_per_s"],
               operations(visible, heads, kv_lora_rank, rope) / peaks["bf16_flops_per_s"])
