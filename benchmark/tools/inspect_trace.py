"""Look at one profiler trace by hand before trusting code written against it.

    python3 benchmark/tools/inspect_trace.py [file.xplane.pb] [events-per-line]

Without a file it reads the newest traced run's capture, which ``run.py
--trace 1`` leaves in ``.kc_cache/bench_trace/`` of its checkout.  Prints every plane and line with its event count, span and first events, then
what ``harness/xplane.py`` makes of the file.
"""

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax.profiler

    from benchmark.harness import xplane

    show = int(argv[2]) if len(argv) > 2 else 5
    path = argv[1] if len(argv) > 1 else sorted(glob.glob(os.path.join(
        ROOT, ".kc_cache", "bench_trace", "plugins", "profile", "*", "*.xplane.pb")))[-1]
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            start = min(e.start_ns for e in events)
            end = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{start * 1e-9:.6f}..{end * 1e-9:.6f} s")
            for e in events[:show]:
                print(f"    {e.name[:100]!r} start={e.start_ns * 1e-9:.6f} "
                      f"dur={e.duration_ns * 1e-9:.6f} {str(dict(e.stats))[:300]}")
    trace = xplane.load(path, host_ops=not any(
        xplane.DEVICE_PLANE.match(p.name) for p in data.planes))
    print("annotations:", {k: len(v) for k, v in trace["annotations"].items()})
    print("reduce:", json.dumps(xplane.reduce(trace), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
