"""The benchmark's one command: one cell, one run, one line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Drives the served solve path — the solver sidecar composed as the deployed
binary composes it, from the client's side of a loopback gRPC channel — under
the cell's traffic, checks every answer, and prints as the LAST line of its
standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  ``--trace 0`` reports the
cell's end-to-end metrics with all tracing off; ``--trace 1`` turns on the
program's spans and a short profiler capture and reports its per-layer
metrics.  Earlier lines are for people: set-up split, quartiles, samples.

Which cell, configuration, traffic, generator and per-layer readers run is
data: BENCHMARK.json and the files it names (benchmark/README.md).  This file
holds no cell's name.

It runs on the machine it is started on and needs the chips the cell asks
for: where JAX finds no TPU, or another number of chips, it exits 2 and prints
no result — it never falls back to the CPU.  ``--rehearse`` is the one
exception, for finding faults without the chip: the configuration's tiny
``rehearse`` sizes on whatever JAX finds, ``correct`` always false.  A
rehearsal's numbers say nothing about speed.
"""

import time

T0 = time.perf_counter()  # set-up is counted from here: what a restart costs

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT  # not benchmark/: its modules are reached as benchmark.*
# the newest traced run's capture stays here (git-ignored, beside the
# program's caches) for benchmark/tools/inspect_trace.py to read by hand
TRACE_DIR = os.path.join(ROOT, ".kc_cache", "bench_trace")

EXIT_NO_CHIP = 2
COLD_TIMEOUT_S = 900.0  # a request that may compile
WARM_TIMEOUT_S = 120.0


def say(**fields) -> None:
    print(json.dumps(fields), flush=True)


class Context:
    """What a traffic kind is given."""

    def __init__(self, cell, seed: int, sidecar) -> None:
        self.config, self.traffic = cell.config, cell.traffic
        self.seed = seed
        self.sidecar = sidecar
        self.timeout = COLD_TIMEOUT_S


def _probe_reply(reply: bytes) -> dict:
    """What the answer's msgpack alone costs, on a copy of the last reply:
    the split of ``service_self_s`` and ``client_s`` the program's spans
    cannot give.  Median of three."""
    import msgpack

    from benchmark.harness import stats

    unpack, pack = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        body = msgpack.unpackb(reply)
        t1 = time.perf_counter()
        msgpack.packb(body)
        pack.append(time.perf_counter() - t1)
        unpack.append(t1 - t0)
    return {"reply_unpack_s": stats.median(unpack), "reply_pack_s": stats.median(pack)}


def _host_usage() -> dict:
    """What the process has cost its host so far: where a run is slow for no
    reason the program shows, these say whether the host was."""
    import resource

    u = resource.getrusage(resource.RUSAGE_SELF)
    return {"user_s": u.ru_utime, "sys_s": u.ru_stime, "minor_faults": u.ru_minflt,
            "major_faults": u.ru_majflt, "voluntary_switches": u.ru_nvcsw,
            "involuntary_switches": u.ru_nivcsw}


def _devices(cell, rehearse: bool):
    """JAX's devices where they are the chips the cell asks for, else None."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if (platform != "tpu" and not rehearse) or len(devices) != cell.chips:
        print(f"benchmark: this cell needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {platform!r} device(s); nothing was run", file=sys.stderr)
        return None
    return devices


def _read_trace(profiler, platform: str):
    """The capture's reduction (``xplane.reduce``), or None."""
    from benchmark.harness import xplane

    trace_file = profiler.trace_file()
    if not trace_file:
        return None
    return xplane.reduce(xplane.load(trace_file, host_ops=platform == "cpu"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever JAX finds; never correct")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(levelname)s %(name)s %(message)s")
    traced = bool(args.trace)

    from benchmark.harness import checks, loop, manifest, roofline, stats

    cell = manifest.load_cell(args.workload, args.rehearse)
    try:
        from karpenter_core_tpu import tracing  # the program under test

        from benchmark.harness.sut import Sidecar
    except ImportError as e:
        print(f"benchmark: the program is not importable here: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    devices = _devices(cell, args.rehearse)
    if devices is None:
        return EXIT_NO_CHIP
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    peaks = None if args.rehearse else roofline.peaks_for(device["kind"])
    t_device = time.perf_counter()

    compiles = checks.CompileCounter()
    if traced:
        tracing.enable()
    sidecar = Sidecar(cell.config["types"], cell.config["provisioners"], traced)
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    try:
        ledger = checks.Ledger()
        ctx = Context(cell, args.seed, sidecar)
        kind = manifest.load_kind(cell.traffic["kind"])(ctx)
        t_inputs = time.perf_counter()
        failures = list(kind.setup())
        first_request_s = sidecar.calls[0].client_s
        setup_compiles = compiles.backend_compiles
        # the benchmark's own inputs and reference answers are millions of
        # objects: keep the collector from walking them inside the window
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - T0
        say(setup={
            "imports_and_device_s": t_device - T0,
            "compose_and_inputs_s": t_inputs - t_device,
            "first_request_s": first_request_s,
            "warm_up_s": setup_s - (t_inputs - T0) - first_request_s,
            "setup_s": setup_s,
            "compile_requests": compiles.requests,
            "backend_compiles": setup_compiles,
        }, device=device, seed=args.seed, workload=args.workload,
            allocator={k: v for k, v in os.environ.items()
                       if k == "PYTHONMALLOC" or k.startswith("MALLOC_")})

        # -- the measured window ----------------------------------------------
        ctx.timeout = WARM_TIMEOUT_S
        profiler = loop.Profiler(TRACE_DIR, kind.group) if traced else None
        requests0 = compiles.requests
        usage0 = _host_usage()
        try:
            units, window_s = loop.run(kind, sidecar, args.seconds, profiler)
        finally:
            if profiler is not None:
                profiler.stop()
        compiles_in_window = compiles.requests - requests0
        host = {k: v - usage0[k] for k, v in _host_usage().items()}

        # -- after the window: the checks that cost time ----------------------
        ctx.timeout = COLD_TIMEOUT_S
        last_reply = sidecar.last_reply
        checked = kind.check()
        failures += checked["failures"]
        solved = None  # one library solve: the kernel's real shapes
        kernel_pods = kind.kernel_pods()
        if cell.chips > 1:
            want = tuple((axis, int(n)) for axis, n in cell.config["mesh"])
            bad, solved = checks.bit_identity(kernel_pods, sidecar, want)
            failures += bad
        observed = ledger.observe(sidecar, compiles_in_window)
        failures += checks.verdict(observed)
        window_failures = [f for u in units for f in u.failures]
        failures += window_failures
        calls = [c for u in units for c in u.calls]

        walls = [u.wall_s for u in units]
        gaps = [b.start_s - (a.start_s + a.wall_s) for a, b in zip(units, units[1:])]
        say(window={
            "units": len(units), "units_per_cycle": kind.group, "calls": len(calls),
            "window_s": window_s,
            "unit_wall_quartiles_s": stats.quartiles(walls),
            "highest_percentile": stats.highest_percentile(len(walls)),
            "generator_gap_median_s": stats.median(gaps) if gaps else None,
            "generator_gap_total_s": sum(gaps),
            "reply_mb_median": stats.median([c.reply_bytes / 1e6 for c in calls]),
        }, host=host, program=observed)
        say(samples={"start_s": [round(u.start_s, 4) for u in units],
                     "wall_s": [round(u.wall_s, 5) for u in units],
                     "pods": [u.pods for u in units]})

        values = {
            "pods_per_s": sum(u.pods for u in units) / window_s,
            "request_p50_s": stats.median(walls),
            # whichever tail the manifest names for this cell; None (left out)
            # where the window holds too few requests to carry it
            **{f"request_p{p:g}_s": stats.tail(walls, p) for p in stats.PERCENTILES[1:]},
            "nodes_per_kpod": (1000.0 * checked["nodes"] / checked["pods_placed"]
                               if checked.get("pods_placed") else None),
            "setup_s": setup_s,
        }
        result = {"attempted": len(calls), "failed": len(window_failures)}
        device["memory_peak_bytes"] = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
        wanted = cell.per_layer if traced else cell.end_to_end
        if traced:
            reduction = _read_trace(profiler, device["platform"])
            if reduction is None:
                failures.append("the traced window holds no device operation")
            else:
                device["busy_s"] = reduction["busy_s"]
                device["window_s"] = reduction["window_s"]
                result["breakdown"] = {"device_ops": reduction["device_ops"],
                                       "idle_gaps": reduction["idle_gaps"]}
            if solved is None and kernel_pods is not None:
                solved = checks.library_solve(kernel_pods, sidecar)
            facts = {
                "units": units,
                "counters": {
                    "compiles_in_window": compiles_in_window,
                    "backend_compiles": setup_compiles,
                    "first_request_s": first_request_s,
                    **_probe_reply(last_reply),
                },
                "device": reduction,
                "peaks": peaks,
                "kernel_shapes": (roofline.kernel_shapes(*solved[:3])
                                  if solved is not None else None),
            }
            say(traced={"request_p50_s": values["request_p50_s"],
                        "pods_per_s": values["pods_per_s"],
                        "kernel_shapes": facts["kernel_shapes"],
                        "reduction": reduction and {
                            k: v for k, v in reduction.items()
                            if not isinstance(v, list)}})
            for m in wanted:
                values[m["name"]] = manifest.load_source(m["reader"]["kind"])(m["reader"], facts)
        if failures:
            say(failures=failures[:20])
        result["correct"] = not failures and not args.rehearse
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in wanted if values.get(m["name"]) is not None
        }
        result["device"] = device
        say(**result)
        return 0
    finally:
        sidecar.close()


if __name__ == "__main__":
    sys.exit(main())
