"""Weight-only int8 matmul — Pallas TPU kernel.

The missing piece for int8 decode (in-scan ``dequantize_tree``
re-materializes full-width weights every decode step: ~4.9 s/token at 1.1B,
measured before PR 1 on another toolchain; the kernels run in no benchmark
cell, so on the chip they are not measured — ROADMAP.md A11 / B11): here the int8 codes stream HBM→VMEM at one
byte per weight and dequantize **inside** the matmul tile, so the HBM read
— which bounds decode — is halved vs bf16 weights and the bf16 tensor never
exists in HBM.

Layout contract (utils/quantization.py:quantize): codes are blockwise over
the row-major flat weight, so with ``block_size`` dividing the minor (F)
dim, ``data`` reshapes to [H, F] int8 and ``scale`` to [H, F/block] fp32 —
tile-friendly without any gather.

reference parity: the bnb int8 inference path (reference utils/bnb.py) runs
on fused CUDA kernels; this is its TPU-native equivalent.  Integration into
the model layers (a QuantizedDense that consumes QuantizedTensor leaves) is
tracked in ROADMAP.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _on_tpu
from ..utils.quantization import QuantizedTensor, dequantize


def _k_tile(h: int, block_k: int):
    """Largest lane-aligned (multiple-of-128) divisor of ``h`` that fits in
    ``block_k``, or None.

    An exact divisor tile needs no in-kernel masking; when the best divisor
    is small relative to ``block_k`` (or none exists), the caller switches
    to a full-size tile with a select-zeroed partial last K step instead
    (``masked_k`` in :func:`quantized_matmul`).
    """
    for bk in range(min(block_k, h) // 128 * 128, 0, -128):
        if h % bk == 0:
            return bk
    return None


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc, *, qblock, out_dtype, k_len, masked_k):
    """Grid (M_tiles, F_tiles, K_tiles); K innermost/serial.

    x [bm, bk] bf16; w [bk, bf] int8 codes; s [bf/qblock, bk] fp32 scales
    (transposed so the tile's minor dim is the 128-aligned K — Mosaic's
    (8, 128) tiling rule).  Dequant happens on the VMEM tile: codes *
    per-block scale, broadcast along the quantization block within F.

    ``masked_k``: the K tile does not divide H — select-zero the
    out-of-range contraction rows of the last tile (a select, so NaN
    padding cannot leak through) instead of accumulating padding garbage.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    # fp32 dequant math: Mosaic only supports non-no-op minor-dim insertion
    # (the s[:, :, None] broadcast) for 32-bit types, so the scale expansion
    # stays fp32 and the product casts down to bf16 for the MXU.
    w = w_ref[...].astype(jnp.float32)
    s = s_ref[...].T  # [bk, bf/qblock]
    bk, bf = w.shape
    w = (w.reshape(bk, bf // qblock, qblock) * s[:, :, None]).reshape(bk, bf)
    if masked_k:
        # select-zero the out-of-range contraction rows of the partial last
        # tile (sublane iota — the same pattern as flash's _zero_oob_rows;
        # a select, so NaN scale padding cannot leak).  x needs no in-kernel
        # mask: the caller zero-pads it to the tile multiple.
        rows = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bf), 0)
        w = jnp.where(rows < k_len, w, 0.0)
    acc[:] += jax.lax.dot_general(
        x_ref[...], w.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc[:].astype(out_dtype)


def _qmm_wholef_kernel(x_ref, w_ref, s_ref, o_ref, acc, *, qblock, out_dtype,
                       k_len, masked_k):
    """Decode-shape variant: grid (M_tiles, K_tiles) with the FULL F dim
    resident per w tile.

    Why whole-F: the tiled kernel's w block [bk, bf=512] is, in the
    row-major [H, F] codes array, ``bk`` strided segments of only ``bf``
    bytes each — the DMA engine sustains ~230 GB/s on that pattern at batch
    1 (the r2 measured bound).  A [bk, F] block is ``bk`` *whole contiguous
    rows* — one dense HBM segment — and cuts grid invocations from
    F/bf x H/bk to H/bk.

    Why scale-on-x: out[m,f] = Σ_h x[m,h]·codes[h,f]·s[fb,h] regroups as
    (x·s[fb,:]) @ codes[:, fb-block] per quantization block fb, so the VPU
    touches each *weight* element exactly once (the mandatory int8→bf16
    convert feeding the MXU) instead of three times (fp32 convert, scale
    multiply, bf16 downcast) — at decode the kernel is VPU-bound on that
    per-element work, not DMA-bound, measured 1.3x bf16 with the dequant-
    in-fp32 form.  The tiny [bm, bk] x re-scales per block are noise, and
    the fp32 dequant intermediate disappears from VMEM entirely.  Decode-
    only (m <= 8): at larger m the [bm, F] accumulator stops fitting and
    the MXU-bound tiled kernel double-buffers better.
    """
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    bk, f = w_ref.shape
    x32 = x_ref[...].astype(jnp.float32)  # [bm, bk]
    s = s_ref[...]  # [f/qblock, bk] fp32
    if masked_k:
        # zero the scales of out-of-range contraction rows in the partial
        # last K tile (a select, so NaN scale padding cannot leak; x's own
        # padding is caller-zeroed)
        rows = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows < k_len, s, 0.0)
    for b in range(f // qblock):
        xs = (x32 * s[b:b + 1, :]).astype(jnp.bfloat16)
        acc[:, b * qblock:(b + 1) * qblock] += jax.lax.dot_general(
            xs, w_ref[:, b * qblock:(b + 1) * qblock].astype(jnp.bfloat16),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == pl.num_programs(1) - 1)
    def _done():
        o_ref[...] = acc[:].astype(out_dtype)


# Whole-F w tiles stream in [bk, F] int8 blocks; bound them to ~4 MiB so the
# double-buffered pair (plus x/scale/accumulator, all small) stays inside
# ~16 MiB VMEM.
_WHOLEF_TILE_BYTES = 4 * 1024 * 1024


def _wholef_tiles(h: int, f: int):
    """(bk, masked_k) for the whole-F decode kernel, or None when no
    lane-aligned K tile fits the VMEM budget at this F."""
    budget = min(1024, _WHOLEF_TILE_BYTES // f, h) // 128 * 128
    if budget < 128:
        return None
    bk = _k_tile(h, budget)
    masked_k = False
    if bk is None or (bk < 384 and budget > bk):
        # same divisor-vs-masked policy as the tiled kernel: a small exact
        # divisor loses to a full-budget tile with one select-zeroed tail
        bk, masked_k = budget, True
    return bk, masked_k


def quantized_matmul(x, qt: QuantizedTensor, *, block_m: int = 128, block_k: Optional[int] = None,
                     block_f: Optional[int] = None, out_dtype=None, interpret=None,
                     wholef: Optional[bool] = None):
    """``x @ W`` where W is an int8 :class:`QuantizedTensor` of shape [H, F].

    x: [..., H].  Falls back to ``dequantize + matmul`` for nf4 codes or
    layouts whose quantization block does not divide F (the kernel needs the
    [H, F/block] scale view).  ``wholef``: None auto-picks the whole-F
    contiguous-row decode kernel at m <= 8 (True forces it for tests, False
    pins the tiled kernel); explicit ``block_k``/``block_f`` also pin tiled.
    """
    h, f = qt.shape[-2], qt.shape[-1]
    qblock = qt.block_size
    lead = x.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    if wholef is None:
        # None = unset: an *explicitly* passed block_f/block_k pins the tiled
        # kernel even at the default values
        wholef = m <= 8 and block_k is None and block_f is None
    if block_f is None:
        block_f = 512
    if block_k is None:
        # decode (tiny m): larger K tiles amortize the per-invocation scale
        # transpose + dequant setup; at large m the 512 tile double-buffers
        # better (measured on v5e)
        block_k = 1024 if m <= 8 else 512
    # prefer a tile that divides H exactly (no mask work in the kernel);
    # otherwise take block_k with in-kernel zeroing of the partial last tile
    bk = _k_tile(h, block_k)
    masked_k = False
    aligned_bk = min(block_k // 128 * 128, h // 128 * 128)  # lane-aligned tile
    if aligned_bk > 0 and (bk is None or (bk < 384 and aligned_bk > bk)):
        # No divisor, or only a small one (the measured-bad 128/256 cases —
        # e.g. Llama-7B's 11008): a strictly larger full-size tile with a
        # select-zeroed partial last K step beats the many small serial
        # steps.  Divisors >= 384 stay exact/unmasked: 512 measured better
        # than masked-1024 on v5e decode (the per-tile select costs more
        # than the larger tile saves), and 384 sits in that regime.
        bk, masked_k = aligned_bk, True
    if (
        qt.scheme != "int8"
        or len(qt.shape) != 2
        # the scale view needs whole q-blocks per row.  Partial *F* grid
        # tiles are fine: out-of-range columns only ever receive garbage that
        # the clipped output write discards; partial K tiles are select-
        # zeroed in-kernel (masked_k).
        or f % qblock != 0
        # the in-kernel (bk, nb, qblock) dequant reshape needs a lane-width
        # minor dim — quantize with block_size % 128 == 0 for the kernel path
        or qblock % 128 != 0
        # H below one lane-width has no viable K tile
        or bk is None
    ):
        w = dequantize(qt, jnp.bfloat16)
        return jnp.matmul(x, w).astype(out_dtype or x.dtype)
    if interpret is None:
        interpret = not _on_tpu()
    out_dtype = out_dtype or x.dtype

    wf = _wholef_tiles(h, f) if wholef else None
    if wf is not None:
        bk, masked_k = wf

    x2 = x.reshape(m, h).astype(jnp.bfloat16)
    if masked_k:
        # defined zeros in x's padded K columns: the kernel's partial last
        # w tile is select-zeroed, but 0 * NaN through the dot would still
        # poison the accumulator if x's out-of-range reads were NaN
        pad_k = -h % bk
        if pad_k:
            x2 = jnp.pad(x2, ((0, 0), (0, pad_k)))
    if getattr(qt, "layout", "flat") == "k2d":
        # codes/scales are already stored in the kernel's operand layouts —
        # the decode scan body contains no per-step reshape or transpose
        codes, scales = qt.data, qt.scale
    else:
        codes = qt.data.reshape(h, f)  # int8, row-major: free reshape
        # transposed scale view [F/qblock, H]: minor dim is the 128-aligned K
        scales = qt.scale.reshape(h, f // qblock).T

    bm = min(block_m, max(8, m))
    if wf is not None:
        out = pl.pallas_call(
            functools.partial(_qmm_wholef_kernel, qblock=qblock,
                              out_dtype=out_dtype, k_len=h, masked_k=masked_k),
            name="qmm_wholef",
            grid=(pl.cdiv(m, bm), pl.cdiv(h, bk)),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, k: (i, k)),
                pl.BlockSpec((bk, f), lambda i, k: (k, 0)),
                pl.BlockSpec((f // qblock, bk), lambda i, k: (0, k)),
            ],
            out_specs=pl.BlockSpec((bm, f), lambda i, k: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((m, f), out_dtype),
            scratch_shapes=[pltpu.VMEM((bm, f), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")
            ),
            interpret=interpret,
        )(x2, codes, scales)
        return out.reshape(*lead, f)
    # The transposed-scale block's sublane dim (bf/qblock) must be divisible
    # by 8 or equal the full array dim (Mosaic lowering rule).  Partial last
    # F tiles are fine — their out-of-range columns land in the clipped
    # output write.
    if f <= 8 * qblock:
        bf = f  # single F tile: scale block covers the full (small) dim
    else:
        bf = min(block_f, f)
        bf = max(qblock * 8, (bf // (qblock * 8)) * qblock * 8)

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, qblock=qblock, out_dtype=out_dtype,
                          k_len=h, masked_k=masked_k),
        name="qmm",
        grid=(pl.cdiv(m, bm), pl.cdiv(f, bf), pl.cdiv(h, bk)),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bf), lambda i, j, k: (k, j)),
            pl.BlockSpec((bf // qblock, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bf), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, f), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x2, codes, scales)
    return out.reshape(*lead, f)
