"""python3 perfbench/bounds.py [--sets DIR [--origin TEXT] [--record]] [--bound metric=value ...]

Is every end-to-end bound of ``BENCHMARK.json`` inside the window that the
driver's three refusal reasons define, on ALL the readings known?

A set-spread is the interquartile distance of a set of runs over its median
(``statistics.quantiles(values, n=4)``).  ``perfbench/spreads.json`` records
every set-spread and set-median known for each metric and cell, by CHECK: one
origin measuring the cells on one occasion (a builder's two sets, the driver's
check of a PR).  From them, for each metric:

- floor A: a new cell's runs may spread by at most half of the bound.  For this
  test the driver takes the MEAN of a check's two sets' spreads and leaves out
  each set's run farthest from the median (one far-off run in a set does no
  harm, two do), so the bound is at least 2 x that mean, in the check and cell
  where it is widest (``spread_trimmed`` where the raw runs are known, else the
  spread as recorded).  Twice the widest SINGLE set so trimmed is printed
  beside it: a bound under it has been too tight for one set of six already;
- floor B: the medians of two sets of the same code may not differ by more than
  the bound, so the bound is at least the widest such difference recorded;
- ceiling: the bound may be at most 8 x the widest spread a check reads on any
  workload (of all the runs, none left out), or 1% if that is more; taken on
  the check whose widest spread is the NARROWEST, since the driver's next
  check may read that again.  Never over the contract's cap of 10%.

So a bound holds while the driver's widest spread lies between an eighth and a
half of it ("tolerates").  Exit code 1 when a bound lies outside its window.
``setup_s`` is judged by its median alone (each set's first run, which
compiles, left out): floor B only, and the contract fixes its bound at 0.1.

``--sets DIR`` reduces the result lines of a builder's own sets (files
``<set>.<seed>.out`` anywhere under DIR, as ``perfbench/run.py`` prints them:
the cell's name is on the file's first line) to such readings and prints them;
``--record`` writes them into ``spreads.json`` as the check ``--origin`` names,
replacing one of that name.  ``--bound`` tries another value (what would PR
23's 0.07 have met?).  PERF.md section 2 holds this tool's table."""

from __future__ import annotations

import argparse
import itertools
import json
import statistics
import sys
from pathlib import Path

PB = Path(__file__).resolve().parent
ROOT = PB.parent
SPREADS = PB / "spreads.json"
CAP = 0.1            # the contract's largest bound
LEAST = 0.01         # and its smallest: a bound of 1% is never too loose


def set_spread(values) -> float:
    """Interquartile distance over the median, as the contract defines it."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values) -> list:
    """The set without its run farthest from the median (the driver's
    tightness test leaves that one out of each set)."""
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    return [v for i, v in enumerate(values) if i != far]


def read_sets(directory: Path) -> dict:
    """cell -> set name -> metric -> values in the order the runs were made
    (file modification time), from every ``<set>.<seed>.out`` under
    ``directory`` whose last line is a ``--trace 0`` result line."""
    out: dict = {}
    for path in sorted(directory.rglob("*.out"), key=lambda p: (p.stat().st_mtime, p.name)):
        lines = [l for l in path.read_text().splitlines() if l.startswith("{")]
        if len(lines) < 2:
            continue
        first, last = json.loads(lines[0]), json.loads(lines[-1])
        if first.get("phase") != "start" or first.get("trace") or "metrics" not in last:
            continue
        if not last["correct"]:
            raise SystemExit(f"bounds: {path} is a run with correct = false")
        per_set = out.setdefault(first["workload"], {}).setdefault(path.name.split(".")[0], {})
        for metric, m in last["metrics"].items():
            per_set.setdefault(metric, []).append(m["value"])
    return out


def readings_of(sets: dict) -> list:
    """The readings of one check from its raw runs.  ``setup_s`` leaves each
    set's first run out (the one that may compile)."""
    readings = []
    for cell, by_set in sorted(sets.items()):
        for metric in sorted({m for s in by_set.values() for m in s}):
            rows = []
            for name, values in sorted(by_set.items()):
                v = values.get(metric, [])
                v = v[1:] if metric == "setup_s" else v
                if len(v) < 2:
                    continue
                rows.append({"set": name, "runs": len(v), "median": statistics.median(v),
                             "spread": set_spread(v),
                             "spread_trimmed": set_spread(trimmed(v) if len(v) > 2 else v)})
            if rows:
                readings.append({"metric": metric, "cell": cell, "sets": rows})
    return readings


def window(metric: str, bound: float, checks: list) -> dict:
    """Floors, ceiling and verdict of one metric's bound over every check."""
    tight, single, gaps, per_check = [], [], [], []
    for check in checks:
        exact, at_least = [], []
        for r in check["readings"]:
            if r["metric"] != metric or r.get("use") is False:
                continue
            sets = [s for s in r["sets"] if "spread" in s and s.get("is", "exact") != "at_most"]
            if sets:                     # an upper end is no reading of how wide the runs spread
                trimmed_ = [s.get("spread_trimmed", s["spread"]) for s in sets]
                tight.append((statistics.mean(trimmed_), check["origin"], r["cell"]))
                single.append(max(trimmed_))
            for s in sets:
                (exact if s.get("is", "exact") == "exact" else at_least).append(s["spread"])
            medians = [s["median"] for s in r["sets"] if "median" in s]
            if metric == "setup_s":      # only a second set WORSE than the first counts
                gaps += [((b - a) / a, check["origin"], r["cell"]) for a, b in zip(medians, medians[1:])]
            else:
                gaps += [(abs(a - b) / min(a, b), check["origin"], r["cell"])
                         for a, b in itertools.combinations(medians, 2)]
        if exact or at_least:            # one-sided only: the check's widest may be just that
            per_check.append((max(exact or at_least), check["origin"]))
    none = (0.0, "-", "-")
    floor_b = max(gaps, default=none)
    if metric == "setup_s":
        floor_a, ceiling, narrowest = none, CAP, (None, "-")
    else:
        floor_a = max(tight, default=none)
        floor_a = (2 * floor_a[0],) + floor_a[1:]
        narrowest = min(per_check, default=(None, "-"))
        ceiling = CAP if narrowest[0] is None else min(CAP, max(LEAST, 8 * narrowest[0]))
    low = max(floor_a[0], floor_b[0], LEAST)
    return dict(metric=metric, bound=bound, floor_a=floor_a, floor_b=floor_b, ceiling=ceiling,
                narrowest=narrowest, low=low, inside=low <= bound <= ceiling,
                tolerates=(0.0 if bound <= LEAST else bound / 8, bound / 2),
                single=2 * max(single, default=0.0), centre=(low * ceiling) ** 0.5)


def pct(x: float) -> str:
    return f"{x * 100:.4g}%"


def table(rows: list) -> str:
    head = ("| metric | bound | floor A: 2 x the sets' mean spread, farthest run left out (where) | floor B: widest gap of two "
            "sets' medians (where) | ceiling: 8 x narrowest check's widest (where) | driver-side "
            "spreads tolerated | 2 x widest single set | verdict | window's centre |\n|---|---|---|---|---|---|---|---|---|")
    lines = [head]
    for w in rows:
        if w["metric"] == "setup_s":
            a = c = tol = single = "- (judged by its median alone)"
        else:
            a = f"{pct(w['floor_a'][0])} ({w['floor_a'][1]}; {w['floor_a'][2]})"
            c = f"{pct(w['ceiling'])} ({w['narrowest'][1]})"
            tol = f"{pct(w['tolerates'][0])} .. {pct(w['tolerates'][1])}"
            single = pct(w["single"])
        b = f"{pct(w['floor_b'][0])} ({w['floor_b'][1]}; {w['floor_b'][2]})"
        lines.append(f"| `{w['metric']}` | {pct(w['bound'])} | {a} | {b} | {c} | {tol} | {single} | "
                     f"{'inside' if w['inside'] else 'NO WINDOW' if w['low'] > w['ceiling'] else 'OUTSIDE'} [{pct(w['low'])}, {pct(w['ceiling'])}] | {pct(w['centre'])} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sets", type=Path, help="a directory of result lines to reduce to readings")
    ap.add_argument("--origin", default="my chip runs", help="the name of the check --sets is")
    ap.add_argument("--record", action="store_true", help="write --sets into spreads.json")
    ap.add_argument("--bound", action="append", default=[], metavar="METRIC=VALUE")
    args = ap.parse_args(argv)

    recorded = json.loads(SPREADS.read_text())
    checks = recorded["checks"]
    if args.sets:
        mine = {"origin": args.origin, "readings": readings_of(read_sets(args.sets))}
        print(json.dumps(mine, indent=1))
        checks = [c for c in checks if c["origin"] != args.origin] + [mine]
        if args.record:
            SPREADS.write_text(json.dumps({**recorded, "checks": checks}, indent=1) + "\n")
    bounds = {m["name"]: m["bound"] for m in json.loads((ROOT / "BENCHMARK.json").read_text())["end_to_end"]}
    for item in args.bound:
        name, value = item.split("=")
        bounds[name] = float(value)      # also a metric on record that is not (or no longer) judged
    rows = [window(name, bound, checks) for name, bound in bounds.items()]
    print(table(rows))
    outside = [w["metric"] for w in rows if not w["inside"]]
    if outside:
        print(f"bounds: outside their windows: {', '.join(outside)}", file=sys.stderr)
    return 1 if outside else 0


if __name__ == "__main__":
    sys.exit(main())
