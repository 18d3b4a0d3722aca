"""Measured throughput of an XLA host-compute region on the REAL worker
host — the denominator of the 7B-offload accounting.

The 7B step's host region performs a lion-shaped streaming update over the
pinned-host masters/momentum; this probe times the same op shape (read
fp32 master + bf16 momentum + bf16 grad, write fp32 master + bf16
momentum) over a 1 GiB master tree as a whole program, giving effective
GiB/s of the worker host's memory system under XLA host compute.  (Host
regions execute on the machine that holds the chip, so a numpy STREAM on
any other box measures the wrong machine.)

The measurement kernel itself is
``accelerate_tpu.utils.environment.calibrate_host_compute`` — the SAME
function the quiet-box gate's 1-s calibration chain runs, just at 1-GiB
granularity and ``--streams`` independent regions, so the calibration and
the baseline it is compared against can never drift onto different
kernels.

The probe ENFORCES the quiet-box precondition (VERDICT r5 weak #7: the
same binary measured 0.35-1.61 GiB/s depending on operator-box load):
a loadavg gate plus the calibration chain compared against the
documented 1.71 GiB/s quiet baseline run first, and the probe refuses on
a loaded/degraded box unless ``--force`` is passed.  The gate report is
always included in the output JSON so every archived number carries its
own validity evidence."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=1,
                    help="number of INDEPENDENT host regions in one program "
                         "(disjoint trees, no data deps): measures whether the "
                         "worker host's bandwidth scales with host-region "
                         "concurrency — the 7B chunked update currently "
                         "token-serializes into one chain")
    ap.add_argument("--gib", type=float, default=1.0,
                    help="fp32 master GiB per stream")
    ap.add_argument("--force", action="store_true",
                    help="measure anyway on a loaded/degraded box (the gate "
                         "report still lands in the output JSON)")
    args = ap.parse_args()

    from accelerate_tpu.utils.environment import calibrate_host_compute, quiet_box_gate

    gate = quiet_box_gate()
    if not gate["ok"]:
        for w in gate["warnings"]:
            print(f"host_compute_probe: {w}", file=sys.stderr)
        if not args.force:
            print(json.dumps({
                "metric": "worker_host_compute_bandwidth",
                "unit": "GiB/s",
                "refused": True,
                "quiet_box": gate,
            }))
            sys.exit(2)

    rep = calibrate_host_compute(gib=args.gib, iters=4, streams=args.streams)
    print(json.dumps({
        "metric": "worker_host_compute_bandwidth",
        "unit": "GiB/s",
        "streams": rep["streams"],
        "gib_per_stream": args.gib,
        "aggregate_gib_s": rep["gibs"],
        "secs_per_iter": rep["secs_per_iter"],
        "backend": jax.default_backend(),
        "quiet_box": gate,
    }))


if __name__ == "__main__":
    main()
