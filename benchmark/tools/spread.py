"""Measure the run-to-run spread as the driver does, from saved runs.

    python3 benchmark/tools/spread.py [--sets 2] [--prefix 20,35] run1.out run2.out ...

Each file is the standard output of one ``benchmark/run.py`` run.  Runs are
grouped by workload and split, in the order given, into ``--sets`` sets.  For
every end-to-end metric it prints each set's median, quartiles and spread
(quartile distance over median), the wider of the spreads, and how far the
second set's median lies from the first's.  A bound is about five times the
widest spread over the cells, never under 1 %.

``--prefix`` re-reads each run's per-unit samples as if the window had been
that many seconds long (same end rule: the first cycle boundary at or after
it), which is how one set of long runs also answers for shorter
``run_seconds``.  ``nodes_per_kpod`` and ``setup_s`` do not depend on the
window's length.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.harness import stats  # noqa: E402


def read_run(path: str) -> dict:
    run = {}
    with open(path) as f:
        for line in f:
            if not line.startswith("{"):
                continue
            body = json.loads(line)
            for key in ("setup", "samples", "window", "metrics"):
                if key in body:
                    run[key] = body[key]
            if "workload" in body:
                run["workload"] = body["workload"]
            if "correct" in body:
                run["correct"] = body["correct"]
    return run


def prefix_metrics(run: dict, seconds: float, group: int) -> dict:
    s = run["samples"]
    n = len(s["wall_s"])
    ends = [a + b for a, b in zip(s["start_s"], s["wall_s"])]
    stop = next((i for i in range(group, n + 1, group) if ends[i - 1] >= seconds), n)
    walls = s["wall_s"][:stop]
    out = {"pods_per_s": sum(s["pods"][:stop]) / ends[stop - 1],
           "request_p50_s": stats.median(walls)}
    for p in stats.PERCENTILES[1:]:  # as run.py names them; None where unreadable
        tail = stats.tail(walls, p)
        if tail is not None:
            out[f"request_p{p:g}_s"] = tail
    out["_units"] = stop
    return out


def table(rows: dict, sets: int) -> None:
    for metric, values in rows.items():
        size = max(len(values) // sets, 1)
        parts = [values[i * size:(i + 1) * size] for i in range(sets)]
        parts = [p for p in parts if p]
        spreads = [stats.spread(p) for p in parts]
        medians = [stats.median(p) for p in parts]
        shift = (medians[-1] - medians[0]) / medians[0] if len(parts) > 1 and medians[0] else 0.0
        print(f"  {metric:16s} n={len(values):2d} median={stats.median(values):.6g} "
              f"quartiles=({stats.quantile(values, .25):.6g}, {stats.quantile(values, .75):.6g}) "
              f"spread/set={[round(100 * x, 2) for x in spreads]}% "
              f"widest={100 * max(spreads):.2f}% shift={100 * shift:+.2f}%")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--prefix", default="", help="comma-separated window lengths")
    ap.add_argument("files", nargs="+")
    args = ap.parse_args(argv)
    by_cell: dict = {}
    for path in args.files:
        run = read_run(path)
        if "metrics" in run:
            by_cell.setdefault(run["workload"], []).append(run)
    for workload, runs in by_cell.items():
        print(f"{workload}: {len(runs)} runs, correct={[r['correct'] for r in runs]}, "
              f"units={[r['window']['units'] for r in runs]}")
        rows: dict = {}
        for r in runs:
            for name, m in r["metrics"].items():
                rows.setdefault(name, []).append(m["value"])
        table(rows, args.sets)
        for seconds in [float(x) for x in args.prefix.split(",") if x]:
            rows = {}
            for r in runs:
                group = r["window"].get("units_per_cycle", 1)
                for name, value in prefix_metrics(r, seconds, group).items():
                    rows.setdefault(name, []).append(value)
            print(f" as if run_seconds={seconds:g} (units {rows.pop('_units')}):")
            table(rows, args.sets)
    return 0


if __name__ == "__main__":
    sys.exit(main())
