"""Minimal XSpace (``*.xplane.pb``) reader + per-op time aggregation.

The torch reference exposes ``prof.key_averages()`` — a per-op self-time
table — straight from ``torch.profiler`` (reference utils/dataclasses.py:484
ProfileKwargs → torch.profiler.profile).  On TPU the captured artifact is an
XSpace protobuf that normally needs TensorBoard's profile plugin to read;
this module decodes it directly with a hand-rolled protobuf **wire-format**
parser (no tensorflow/tensorboard dependency — only the stable public
field numbers of ``xplane.proto``), so ``TPUProfiler.key_averages()`` can
print an op-class breakdown in-process.

Wire-format subset: varint (0) and length-delimited (2) fields are enough —
every XSpace field we read is one of the two (fixed64/fixed32 are skipped
structurally).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from functools import lru_cache
from typing import Optional


@lru_cache(maxsize=16)
def _cached_planes(path: str, size: int, mtime_ns: int) -> tuple:
    """Parsed planes per file, keyed by (path, size, mtime), so that
    ``op_class_breakdown`` and the two overlap reports decode each artifact
    once (the pure-Python wire parse of a real trace costs seconds)."""
    return tuple(parse_xspace(path))


def _planes_of(path: str) -> tuple:
    st = os.stat(path)
    return _cached_planes(path, st.st_size, st.st_mtime_ns)


def _read_varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) over a message buffer.
    value is an int for varint fields, a memoryview for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _read_varint(buf, i)
        fnum, wtype = key >> 3, key & 7
        if wtype == 0:  # varint
            val, i = _read_varint(buf, i)
        elif wtype == 2:  # length-delimited
            ln, i = _read_varint(buf, i)
            val = memoryview(buf)[i:i + ln]
            i += ln
        elif wtype == 5:  # fixed32
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        elif wtype == 1:  # fixed64
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        else:  # pragma: no cover - groups are absent from xplane.proto
            raise ValueError(f"unsupported wire type {wtype}")
        yield fnum, wtype, val


@dataclass
class Line:
    name: str = ""
    events: list = field(default_factory=list)  # (metadata_id, duration_ps)


@dataclass
class Plane:
    name: str = ""
    event_names: dict = field(default_factory=dict)  # id -> name
    lines: list = field(default_factory=list)


def _parse_event(buf) -> tuple[int, int]:
    meta_id = dur_ps = 0
    for fnum, _, val in _fields(bytes(buf)):
        if fnum == 1:
            meta_id = val
        elif fnum == 3:
            dur_ps = val
    return meta_id, dur_ps


def _parse_line(buf) -> Line:
    line = Line()
    for fnum, _, val in _fields(bytes(buf)):
        if fnum == 2:
            line.name = bytes(val).decode("utf-8", "replace")
        elif fnum == 11 and not line.name:
            line.name = bytes(val).decode("utf-8", "replace")
        elif fnum == 4:
            line.events.append(_parse_event(val))
    return line


def _parse_event_metadata_entry(buf) -> tuple[int, str]:
    """map<int64, XEventMetadata> entry: key=1, value=2 (XEventMetadata)."""
    key, name = 0, ""
    for fnum, _, val in _fields(bytes(buf)):
        if fnum == 1:
            key = val
        elif fnum == 2:
            for f2, _, v2 in _fields(bytes(val)):
                if f2 == 2:
                    name = bytes(v2).decode("utf-8", "replace")
    return key, name


def _parse_plane(buf) -> Plane:
    plane = Plane()
    for fnum, _, val in _fields(bytes(buf)):
        if fnum == 2:
            plane.name = bytes(val).decode("utf-8", "replace")
        elif fnum == 3:
            plane.lines.append(_parse_line(val))
        elif fnum == 4:
            k, name = _parse_event_metadata_entry(val)
            plane.event_names[k] = name
    return plane


def parse_xspace(path: str) -> list[Plane]:
    with open(path, "rb") as f:
        data = f.read()
    return [_parse_plane(val) for fnum, _, val in _fields(data) if fnum == 1]


def find_xplane_files(trace_dir: str) -> list[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))


def _device_planes(planes: list[Plane], device_substr: str) -> list[Plane]:
    dev = [p for p in planes if device_substr.lower() in p.name.lower()]
    if not dev:
        dev = [p for p in planes if "/device:" in p.name]
    if not dev:
        dev = [p for p in planes if p.name.startswith("/host:CPU")]
    return dev


def _line_times(trace_dir: str, device_substr: str, line_name: str,
                fallback_all: bool = False) -> dict[str, float]:
    """ms per op name, summed over lines named ``line_name``.  With
    ``fallback_all``, a plane with no such line contributes all its lines
    (backends without the TPU line naming, e.g. the CPU tests)."""
    totals: dict[str, float] = defaultdict(float)
    for path in find_xplane_files(trace_dir):
        for plane in _device_planes(list(_planes_of(path)), device_substr):
            matching = [ln for ln in plane.lines if ln.name == line_name]
            if not matching and fallback_all:
                matching = plane.lines
            for line in matching:
                for meta_id, dur_ps in line.events:
                    name = plane.event_names.get(meta_id, f"op_{meta_id}")
                    totals[name] += dur_ps / 1e9  # ps -> ms
    return dict(totals)


def device_op_times(trace_dir: str, device_substr: str = "TPU") -> dict[str, float]:
    """Total device time per HLO op (ms) from the per-op timeline only (the
    ``XLA Ops`` line).  ``Steps`` / ``XLA Modules`` are whole-program parent
    spans and ``Async XLA Ops`` are overlapped transfers — counting either
    alongside the ops would double-book the wall clock."""
    return _line_times(trace_dir, device_substr, "XLA Ops", fallback_all=True)


def async_copy_ms(trace_dir: str, device_substr: str = "TPU") -> float:
    """Total duration on the ``Async XLA Ops`` line — DMA/copy traffic that
    the scheduler overlapped with compute.  Reported separately: it costs
    bandwidth, not (necessarily) wall clock."""
    t = _line_times(trace_dir, device_substr, "Async XLA Ops")
    return round(sum(t.values()), 3)


def steps_ms(trace_dir: str, device_substr: str = "TPU") -> float:
    """Total duration of the ``Steps`` parent spans (the traced wall time)."""
    t = _line_times(trace_dir, device_substr, "Steps")
    return round(sum(t.values()), 3)


# ---------------------------------------------------------------------------
# op-class attribution
# ---------------------------------------------------------------------------

_SUFFIX_RE = re.compile(r"\.[0-9]+(\.remat)?$")


def _lhs_base(name: str) -> str:
    """`%convolution_add_fusion.82 = ...` -> `convolution_add_fusion`."""
    lhs = name.split(" = ")[0].lstrip("%").strip()
    return _SUFFIX_RE.sub("", lhs)


def classify_op(name: str) -> str:
    """Map one HLO event name to an op class.

    Heuristics tuned against v5e train-step traces of this package (taken
    before PR 1 of CHANGES.md; the benchmark classifies with its own copy,
    ``perfbench/trace_reduce.py``): Pallas kernels surface as
    ``custom-call``s whose instruction keeps the model scope name (``self_attn`` = flash
    attention); projection/embedding matmuls are the ``convolution*``/
    ``dot*`` fusions plus XLA:TPU's *unnamed* ``fusion.N`` output fusions
    (named elementwise fusions spell their root ops instead, e.g.
    ``multiply_reduce_fusion``); the fused-CE vocab-chunk loop runs as
    ``while`` ops."""
    base = _lhs_base(name)
    low = base.lower()
    if "self_attn" in low or "flash" in low or "mha" in low:
        return "flash_attention"
    if "int8" in low or "quant" in low:
        return "int8_kernel"
    if "custom-call" in low:
        return "pallas_other"
    if any(k in low for k in ("all-reduce", "all-gather", "reduce-scatter",
                              "all-to-all", "collective-permute")):
        return "collective"
    full = name.lower()
    if low.startswith("call"):
        # XLA host-compute regions (compute_on("device_host")) surface as
        # call / call-start / call-done spans whose operand layouts carry
        # host-space markers (S(5) memory space / L(...) linear layouts) —
        # at 7B-offload these ARE the step (the chunked host optimizer
        # update + its PCIe transfers).  Device-side call subcomputations
        # (no host markers) stay out of the host bucket.
        if "s(5)" in full or "l(1024)" in full:
            return "host_compute"
        return "other"
    if low.startswith(("copy", "send", "recv", "infeed", "outfeed")):
        return "copy"
    if low.startswith("while"):
        return "while_loops"
    if "dynamic-update" in low or "dynamic-slice" in low or low.startswith(("scatter", "gather")):
        return "dynamic_slice"
    if low.startswith(("convolution", "dot", "einsum")):
        return "matmul"
    if low.startswith("fusion"):
        # unnamed output fusions: on TPU these are the matmul-rooted ones
        # (elementwise fusions carry their root-op names)
        return "matmul"
    if "fusion" in low:
        return "elementwise_fusion"
    if low.startswith("convert"):
        return "convert"
    return "other"


def op_class_breakdown(trace_dir: str, device_substr: str = "TPU") -> dict:
    """{class: {"ms": total, "share": fraction}, ...} plus ``_total_ms``,
    ``_steps_ms`` (traced wall) and ``_async_copy_ms`` (overlapped DMA) —
    the table docs/performance.md's MFU attribution is built from.
    Shares are of the op-timeline total; ``while`` spans can double-book
    their inner ops by a few percent (XLA emits both)."""
    per_op = device_op_times(trace_dir, device_substr)
    per_class: dict[str, float] = defaultdict(float)
    for name, ms in per_op.items():
        per_class[classify_op(name)] += ms
    total = sum(per_class.values())
    denom = total or 1.0  # guard only the division — _total_ms stays honest
    out = {
        cls: {"ms": round(ms, 3), "share": round(ms / denom, 4)}
        for cls, ms in sorted(per_class.items(), key=lambda kv: -kv[1])
    }
    out["_total_ms"] = round(total, 3)
    out["_steps_ms"] = steps_ms(trace_dir, device_substr)
    out["_async_copy_ms"] = async_copy_ms(trace_dir, device_substr)
    return out


def streaming_overlap_report(trace_dir: str, device_substr: str = "TPU",
                             breakdown: Optional[dict] = None) -> dict:
    """Transfer-vs-compute occupancy from a captured trace — the MEASURED
    counterpart of ``ops/streaming``'s predicted overlap accounting.

    ``overlap_frac`` is the share of DMA the latency-hiding scheduler kept
    off the critical path (async copies vs all copy traffic);
    ``transfer_occupancy``/``host_occupancy``/``compute_occupancy`` are
    shares of the op-timeline total, so a host-bound offload step shows up
    as host_occupancy ≈ 1 with its transfers hidden (overlap_frac → 1), and
    a serialized streamed decode as transfer_occupancy ≈ 1 with
    overlap_frac → 0.  Pass an already-computed ``op_class_breakdown``
    result as ``breakdown`` to skip re-aggregating the (parse-cached)
    planes."""
    br = breakdown if breakdown is not None else op_class_breakdown(trace_dir, device_substr)
    copy_inline = br.get("copy", {}).get("ms", 0.0)
    host_ms = br.get("host_compute", {}).get("ms", 0.0)
    total = br["_total_ms"]
    async_ms = br["_async_copy_ms"]
    denom = total or 1.0
    all_copy = copy_inline + async_ms
    # twin registry: MEASURED overlap (predicted side:
    # ops/streaming.offload_transfer_accounting)
    from ..telemetry import twin_registry

    twin_registry().record_measured(
        "offload_transfer.overlap_frac",
        async_ms / all_copy if all_copy else 0.0,
        source="utils/xplane.streaming_overlap_report",
    )
    return {
        "total_ms": total,
        "steps_ms": br["_steps_ms"],
        "copy_ms_inline": round(copy_inline, 3),
        "copy_ms_async": round(async_ms, 3),
        "host_compute_ms": round(host_ms, 3),
        "transfer_occupancy": round(copy_inline / denom, 4),
        "host_occupancy": round(host_ms / denom, 4),
        "compute_occupancy": round(max(0.0, total - copy_inline - host_ms) / denom, 4),
        "overlap_frac": round(async_ms / all_copy, 4) if all_copy else 0.0,
        "kind": "measured",
    }


# HLO name fragments that mark ICI collective traffic (the op classes the
# ring collective-matmul either emits — collective-permute — or replaces)
_COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                     "all-to-all", "collective-permute")


def _is_collective(name: str) -> bool:
    base = _lhs_base(name).lower()
    return any(m in base for m in _COLLECTIVE_MARKS)


def async_collective_ms(trace_dir: str, device_substr: str = "TPU") -> float:
    """Collective time on the ``Async XLA Ops`` line — ICI traffic the
    latency-hiding scheduler kept off the critical path (on TPU the ring's
    ``collective-permute-start``/``done`` pairs land here when hidden)."""
    t = _line_times(trace_dir, device_substr, "Async XLA Ops")
    return round(float(sum(ms for n, ms in t.items() if _is_collective(n))), 3)


def ici_overlap_report(trace_dir: str, device_substr: str = "TPU",
                       breakdown: Optional[dict] = None) -> dict:
    """ICI comm-vs-compute occupancy from a captured trace — the MEASURED
    counterpart of ``ops/collective_matmul.tp_comm_accounting``.

    ``tp_overlap_frac`` is the share of collective time the scheduler hid
    under compute (async vs all collective traffic); ``collective_occupancy``
    is the inline (critical-path) collective share of the op timeline.  A
    well-overlapped ring shows collective_occupancy → 0 with
    tp_overlap_frac → 1; the monolithic path shows its gathers inline.
    Pass an already-computed ``op_class_breakdown`` as ``breakdown`` to skip
    re-aggregating the (parse-cached) planes."""
    br = breakdown if breakdown is not None else op_class_breakdown(trace_dir, device_substr)
    inline = br.get("collective", {}).get("ms", 0.0)
    total = br["_total_ms"]
    async_ms = async_collective_ms(trace_dir, device_substr)
    denom = total or 1.0
    all_coll = inline + async_ms
    # twin registry: MEASURED hidden fraction (predicted side:
    # ops/collective_matmul.tp_comm_accounting)
    from ..telemetry import twin_registry

    twin_registry().record_measured(
        "tp_comm.overlap_frac",
        async_ms / all_coll if all_coll else 0.0,
        source="utils/xplane.ici_overlap_report",
    )
    return {
        "total_ms": total,
        "collective_ms_inline": round(inline, 3),
        "collective_ms_async": round(async_ms, 3),
        "collective_occupancy": round(inline / denom, 4),
        "compute_occupancy": round(max(0.0, total - inline) / denom, 4),
        "tp_overlap_frac": round(async_ms / all_coll, 4) if all_coll else 0.0,
        "kind": "measured",
    }

