"""The device's time by the kernel's named scopes: the WHOLE table.

    python3 benchmark/tools/device_scopes.py [file.xplane.pb] [instructions-per-path]

What the author of the next kernel ``perf_opt`` reads first.  Without a file
it reads the newest traced run's capture, which ``run.py --trace 1`` leaves in
``.kc_cache/bench_trace/`` of its checkout (to keep one, copy it to
``chiprun_out/`` in the same chip call).  Over the traced window (first
``bench.unit`` to the end of the last), chip-averaged SELF seconds
(``harness/sources/device_scopes.py``: how a path is read, and how a nameless
``while`` / ``conditional`` / copy is given the path of what it is nested
with):

    path                         seconds  % busy  inferred  events  largest instructions
    scan/phase.plain/new         0.4321   21.5    0.0120    81920   fusion.12 0.21, ...

then the same seconds by BLOCK — the partition the ``kernel_*_s`` metrics
read (derive, existing, new, committal, record, outside the scan, and the glue
inside the scan that is none of them: ``kernel_glue_s``) and ``unscoped``,
each with how much of it the reader INFERRED (a nameless event given the path
of what it is nested with), the glue split by what it is (the ``cond``s' and
the ``while``'s own time, copies, the rest: the families' quota arithmetic) —
then the two cross-cuts (control, copies), the window's largest instructions
each with its path and result shape, and the unscoped time by instruction.  ``% busy`` is of all op self
time in the window, which is the chips' busy time (``kernel_device_s`` is its
per-unit median).  A table that is all ``unscoped`` is an executable from
before the scopes, or one loaded from a compile cache filled before them:
clear ``<cache_dir>/xla`` and run again.
"""

import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

# the partition the per-layer metrics read, in the manifest's order, and the
# cross-cut: each by the reader its metric ships with
BLOCKS = ("derive", "existing", "new", "committal", "record", "outside_scan", "glue")
CUTS = ("control", "copy")


def spec_of(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "layer_metrics", f"kernel_{name}_s.json")) as f:
        return json.load(f)


def main(argv) -> int:
    from benchmark.harness import annotations
    from benchmark.harness.sources import device_scopes as scopes

    show = int(argv[2]) if len(argv) > 2 else 5
    path = argv[1] if len(argv) > 1 else annotations.newest()
    if not path:
        print("no capture under", annotations.TRACE_DIR, file=sys.stderr)
        return 1
    units = annotations.load(path)["units"]
    details: dict = {}
    chips = scopes.device_ops(path, details=details)
    if not units or not chips:
        print(f"{path}: {len(units)} bench.unit, {len(chips)} chip(s) with ops: nothing to read")
        return 1
    window = (units[0][0], units[-1][1])
    table = scopes.self_seconds(chips, window)
    busy_s = sum(table.values())
    events = collections.Counter(
        key for ops in chips for key, start, end in ops if end > window[0] and start < window[1])
    print(f"{path}\n{len(units)} units, {len(chips)} chip(s), window {window[1] - window[0]:.4f} s, "
          f"busy {busy_s:.4f} s, {sum(events.values())} op events")

    name_of = lambda scope: "/".join(scope) or scopes.UNSCOPED  # noqa: E731
    by_path = collections.defaultdict(collections.Counter)
    inferred_s = collections.Counter()
    count = collections.Counter()
    for (scope, instruction, inferred), s in table.items():
        by_path[name_of(scope)][instruction] += s
        inferred_s[name_of(scope)] += s if inferred else 0.0
    for (scope, _, _), n in events.items():
        count[name_of(scope)] += n
    width = max(len(name) for name in by_path)
    print(f"\n{'path':<{width}}  seconds   % busy  inferred  events   largest instructions")
    for name, row in sorted(by_path.items(), key=lambda kv: -sum(kv[1].values())):
        total = sum(row.values())
        largest = ", ".join(f"{i} {s:.4f}" for i, s in row.most_common(show))
        print(f"{name:<{width}}  {total:8.4f}  {100 * total / busy_s:5.1f}  "
              f"{inferred_s[name]:8.4f}  {count[name] // len(chips):7d}  {largest}")

    print("\nby block (what the kernel_*_s metrics read): seconds, % busy, inferred seconds")
    blocks = [spec_of(name) for name in BLOCKS]
    cuts = [spec_of(name) for name in CUTS]
    unscoped = {"share": "unscoped"}
    inferred = {key: s for key, s in table.items() if key[2]}
    for name, spec in zip(BLOCKS + ("unscoped",), blocks + [unscoped]):
        s = scopes.seconds(spec, table)
        print(f"  {name:<14} {s:8.4f}  {100 * s / busy_s:5.1f}  {scopes.seconds(spec, inferred):8.4f}")
    glue = {key: s for key, s in table.items() if scopes.matches(blocks[-1], key)}
    for name, spec in zip(CUTS, cuts):
        s = scopes.seconds(spec, glue)
        print(f"    glue, {name:<10} {s:8.4f}  {100 * s / busy_s:5.1f}")
    rest = sum(glue.values()) - sum(scopes.seconds(spec, glue) for spec in cuts)
    print(f"    glue, the rest   {rest:8.4f}  {100 * rest / busy_s:5.1f}")
    for name, spec in zip(CUTS, cuts):
        s = scopes.seconds(spec, table)
        print(f"  {name + ' (cut)':<14} {s:8.4f}  {100 * s / busy_s:5.1f}   "
              "self time by instruction name, inside the rows above")

    print("\nper unit: busy, then the blocks, unscoped, the cuts")
    for unit in (scopes.self_seconds(chips, unit) for unit in units):
        cells = [scopes.seconds(spec, unit) for spec in blocks + [unscoped] + cuts]
        print("  " + " ".join(f"{v:8.4f}" for v in [sum(unit.values())] + cells))

    print("\nthe largest instructions: seconds, path, result (two programs may share a name)")
    by_instruction = collections.Counter()
    for (scope, instruction, _), s in table.items():
        by_instruction[(name_of(scope), instruction)] += s
    for (name, instruction), s in by_instruction.most_common(4 * show):
        result = details.get(instruction, "").split(" = ", 1)[-1].split(" ", 1)[0]
        print(f"  {instruction:<28} {s:8.4f}  {name:<40} {result[:60]}")

    print("\nunscoped, by instruction")
    for instruction, s in by_path[scopes.UNSCOPED].most_common(4 * show):
        print(f"  {instruction:<40} {s:8.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
