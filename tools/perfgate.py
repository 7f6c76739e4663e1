"""Per-round performance regression gate (VERDICT r4 #1c).

Runs the headline bench and compares pods/sec against the most recent
``BENCH_r*.json`` recorded on the same platform; fails (exit 1) on a drop
beyond the tolerance.  The reference gates every CI run the same way
(scheduling_benchmark_test.go:178-182); its floor check alone is meaningless
here — a 50x cushion never trips — so this gate tracks drift round-over-round.

Cross-machine honesty: bench records carry a ``machine`` fingerprint
(bench._machine_tag).  When the last same-platform record came
from a different machine the tolerance widens (observed cross-machine spread
on the same code is ~15%), so the gate still catches collapses without
flagging hardware variance as regressions.

Drift verdicts are ADVISORY by default (warn, exit 0): presubmit shares the
machine with whatever else is running, and ambient-load bench noise was
flaking unrelated changes.  Set ``KC_PERF_GATE_STRICT=1`` (CI on a quiet
runner) to make a drift FAIL exit 1 again.  Broken-bench conditions (no
pods_per_sec, bench error) stay hard failures in both modes — those are
bugs, not noise.

Usage: python tools/perfgate.py [--tolerance 0.05] [--record path.json]
"""

import argparse
import glob
import json
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


sys.path.insert(0, REPO)
from bench import run_child  # noqa: E402 - the bench's one process launcher


def run_bench() -> dict:
    """Run ``python bench.py`` on whatever device JAX finds and return its one
    JSON line.  This process never touches JAX, so the bench's own children
    are the only owners of the chip.  A bench that exits non-zero (a phase
    failed) or prints no line is a hard failure."""
    try:
        return run_child([], timeout_s=7200.0)
    except Exception as e:  # noqa: BLE001 - any bench failure fails the gate
        raise SystemExit(f"perfgate bench run failed: {e}")


def last_record(platform: str):
    """Newest BENCH_r*.json whose detail.platform matches, by round number."""
    best = None
    for path in glob.glob(os.path.join(REPO, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", path)
        if not m:
            continue
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        # driver-written records wrap the bench line under "parsed"
        rec = rec.get("parsed") or rec
        detail = rec.get("detail") or {}
        if detail.get("platform") != platform:
            continue
        if detail.get("pods_per_sec") is None:
            continue
        rnd = int(m.group(1))
        if best is None or rnd > best[0]:
            best = (rnd, path, rec)
    return best


# per-stage duration keys compared round-over-round: a stage regression must
# not hide inside a flat top-line (e.g. solve got slower while ingest got
# faster).  Durations — LOWER is better, unlike pods_per_sec.  solve_s and
# decode_s are the de-fused halves of solve_decode_s (bench.py's one
# explicitly-synced pass): decode was 98% of r05 wall time and invisible
# inside the fused number, so each half gates independently ahead of the
# decode pipelining work.  churn_warm_solve_s / churn_full_solve_s are the
# steady-state churn bench's per-tick medians (bench.py churn_line): the
# warm-start delta repair and the full re-solve gate INDEPENDENTLY, so a
# warm-path regression can't hide inside healthy cold numbers (and vice
# versa).  Records older than a split simply lack the keys and are skipped
# per-stage.
STAGE_KEYS = ("solve_decode_s", "solve_s", "decode_s", "ingest_s",
              "classify_s", "planes_s", "upload_s", "encode_s",
              "dispatch_s", "materialize_s", "cold_s",
              "churn_warm_solve_s", "churn_full_solve_s",
              "churn_delta_ingest_s", "objective_s",
              "sharded_solve_s", "sharded_solve_1dev_s",
              "pipeline_warm_tick_s", "pipeline_serial_tick_s",
              "fleet_restore_s", "fleet_replay_s",
              "fusion_repair_solve_s", "fusion_repair_serial_s",
              "relax_solve_s")
# stages that matter enough to flag; the others are printed but only the
# load-bearing ones gate (sub-10ms stages WARN on scheduler-noise otherwise)
# objective_s gates too: the policy scoring stage rides every policy-enabled
# decode, so a regression there is a per-reconcile cost (bench.py policy_line).
# The two sharded stages gate INDEPENDENTLY: the best-mesh solve and its
# 1-device baseline come from bench.py's sharded_line — a sharding
# regression cannot hide inside a flat single-device headline, and a
# baseline regression cannot masquerade as a scaling win.
GATED_STAGES = ("solve_decode_s", "solve_s", "decode_s", "ingest_s", "cold_s",
                # the ingest sub-stages (ISSUE 11) gate INDEPENDENTLY: a
                # classify regression cannot hide inside a flat ingest
                # number, a plane-construction regression cannot hide behind
                # a fast classify, and the per-tick delta ingest cannot
                # silently go O(fleet).  Records older than the split lack
                # the keys and are skipped per-stage, as usual.
                "classify_s", "planes_s", "upload_s", "churn_delta_ingest_s",
                "churn_warm_solve_s", "churn_full_solve_s", "objective_s",
                "sharded_solve_s", "sharded_solve_1dev_s",
                # the pipelined loop's warm per-tick median gates as its own
                # stage (bench.py pipeline_line): an overlap regression —
                # a new sync point, a donation that stopped engaging — must
                # not hide inside healthy solve/decode halves.  The serial
                # twin stays advisory (it moves with machine noise and is
                # already covered by the churn stages).
                "pipeline_warm_tick_s",
                # the fleet checkpoint-restore cost at the deepest chain
                # (bench.py fleet_line): the latency an evicted tenant pays
                # before its first failover answer.  The replay twin stays
                # advisory — it moves with solve cost, which the solve
                # stages already gate.
                "fleet_restore_s",
                # the fused cross-tenant REPAIR dispatch at the deepest
                # tenant count (bench.py fusion_line): the vmapped warm-
                # carry solve the coalescer amortizes steady churn onto.
                # Gates independently of the anchor-batch stage — a repair-
                # fusion regression (a new per-member sync, a stacking copy
                # gone quadratic) must not hide inside healthy anchor
                # coalescing numbers.  The serial twin stays advisory (it
                # moves with solo repair cost, already gated by
                # churn_warm_solve_s).
                "fusion_repair_solve_s",
                # the relaxation family's full pipeline wall (bench.py
                # relax_line: PG solve + rounding + audit + exact repair) at
                # the skewed-fleet size.  Gates independently of the scan
                # stages: a relax-only regression — an extra device sync, a
                # repair window gone full-width — must not hide behind a
                # healthy scan solve_s (the scan twin in the same bench line
                # is already covered by solve_s/churn stages).
                "relax_solve_s")


def compare_stages(detail: dict, prev_detail: dict, tol: float):
    """[(stage, current, previous, regressed)] for stages present in both
    records.  ``regressed`` = current exceeds previous by more than ``tol``
    (fractional) AND more than an absolute 50 ms noise floor."""
    rows = []
    for key in STAGE_KEYS:
        cur, prev = detail.get(key), prev_detail.get(key)
        if cur is None or prev is None:
            continue
        regressed = (
            key in GATED_STAGES
            and cur > prev * (1.0 + tol)
            and cur - prev > 0.05
        )
        rows.append((key, float(cur), float(prev), regressed))
    return rows


def gate_analysis_budget(budget_s: float = 30.0) -> int:
    """The static-analysis suite rides every presubmit (`make verify`
    runs kcanalyze --strict), so its wall time is a perf surface like any
    other stage: hard-fail when the whole pass suite blows the 30 s
    presubmit budget.  Runs ``kcanalyze --json`` in a subprocess — running
    the passes in-process would hide their real cold-start cost behind this
    process's already-warm imports."""
    import subprocess

    cmd = [sys.executable, os.path.join(REPO, "tools", "kcanalyze.py"),
           "--json"]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    try:
        report = json.loads(proc.stdout)
    except json.JSONDecodeError:
        print("perfgate: FAIL kcanalyze --json produced no report "
              f"(rc={proc.returncode}): {proc.stderr.strip()[:200]}")
        return 1
    total = float(report.get("total_s") or 0.0)
    slowest = sorted(report.get("passes", ()),
                     key=lambda p: -p["seconds"])[:3]
    names = ", ".join(f"{p['name']} {p['seconds']:.1f}s" for p in slowest)
    print(f"perfgate: analysis suite {total:.1f}s over "
          f"{report.get('files')} file(s) "
          f"(budget {budget_s:.0f}s; slowest: {names})")
    if not report.get("ok", False):
        print("perfgate: note kcanalyze reported findings — `make verify` "
              "gates those; this stage only gates the time budget")
    if total >= budget_s:
        print(f"perfgate: FAIL analysis suite {total:.1f}s blew the "
              f"{budget_s:.0f}s presubmit budget — a pass went quadratic "
              "(per-pass timings above point at the culprit)")
        return 1
    return 0


def warn_compile_budget(detail: dict) -> None:
    """Advisory tie between the static retrace budget and the measured run:
    warn when the bench's observed XLA compile count exceeds the manifest's
    expected cold-compile count (karpenter_core_tpu/analysis/
    retrace_budget.json).  Warn-only — ambient cache state (a cleared
    ~/.cache, a kernel edit invalidating the export cache) legitimately
    moves the number; the per-test budgets in tests/conftest.py are the
    enforced layer."""
    from karpenter_core_tpu.analysis.manifest import load_retrace_manifest

    observed = detail.get("compiles")
    try:
        expected = int(load_retrace_manifest().get("bench_cold_compiles", 0) or 0)
    except (TypeError, ValueError):
        expected = 0
    if observed is None or not expected:
        return
    if observed > expected:
        print(
            f"perfgate: WARNING bench observed {observed} XLA compiles > "
            f"manifest expected cold-compile count {expected} — a retrace "
            "crept into the hot path (see docs/ANALYSIS.md retrace-budget)"
        )
    else:
        print(f"perfgate: compile count {observed} within manifest "
              f"budget {expected}")


def report_churn(detail: dict) -> None:
    """Surface the incremental-solve churn line: the full/delta decision
    counts, the measured speedup, and assignment parity.  Advisory — the
    enforced side is the two churn stage durations in GATED_STAGES."""
    churn = detail.get("churn")
    if not churn:
        return
    if "error" in churn:
        print(f"perfgate: churn bench errored: {churn['error']}")
        return
    print(
        "perfgate: churn warm_solve {w:.4f}s vs full_resolve {f:.4f}s — "
        "speedup {s:.2f}x, modes {m}, identical_assignments={i}".format(
            w=churn["warm_solve_s"], f=churn["full_resolve_s"],
            s=churn.get("speedup", 0.0), m=churn.get("modes"),
            i=churn.get("identical_assignments"),
        )
    )
    if churn.get("delta_ingest_s") is not None:
        frac = churn.get("delta_ingest_fraction_of_full")
        print(
            "perfgate: churn delta ingest {d:.5f}s for {n} churned pods "
            "(full re-ingest {f:.4f}s, fraction {r})".format(
                d=churn["delta_ingest_s"],
                n=churn.get("churned_pods_per_tick"),
                f=churn.get("full_ingest_s") or 0.0,
                r=frac,
            )
        )
        # O(churned) acceptance: at 2% churn the delta tick must cost a
        # small fraction of the O(fleet) re-ingest (ISSUE 11)
        if frac is not None and frac > 0.5:
            print(
                "perfgate: WARNING churn delta ingest cost is approaching "
                "the O(fleet) re-ingest — the membership-delta path is not "
                "paying for itself"
            )
    if churn.get("speedup", 0.0) < 2.0:
        print(
            "perfgate: WARNING churn speedup below the 2x ISSUE-7 acceptance "
            "floor — the warm-start delta path is not paying for itself"
        )


def report_pipeline(detail: dict) -> None:
    """Surface the pipelined-loop line (bench.py pipeline_line): serial vs
    double-buffered per-tick medians, the hidden-fetch fraction, the
    donation ledger, and assignment parity.  Advisory — the enforced side
    is ``pipeline_warm_tick_s`` in GATED_STAGES."""
    pipeline = detail.get("pipeline")
    if not pipeline:
        return
    if "error" in pipeline:
        print(f"perfgate: pipeline bench errored: {pipeline['error']}")
        return
    print(
        "perfgate: pipeline warm tick {p:.4f}s vs serial {s:.4f}s — "
        "speedup {x:.2f}x, overlap_efficiency={e}, donated={d}, "
        "donation_reallocs={r}, identical_assignments={i}".format(
            p=pipeline["pipelined_tick_s"], s=pipeline["serial_tick_s"],
            x=pipeline.get("speedup", 0.0),
            e=pipeline.get("overlap_efficiency"),
            d=pipeline.get("donated"),
            r=pipeline.get("donation_reallocs"),
            i=pipeline.get("identical_assignments"),
        )
    )
    eff = pipeline.get("overlap_efficiency")
    if eff is not None and eff < 0.5:
        print(
            "perfgate: WARNING pipeline overlap efficiency below 0.5 — most "
            "of the decode fetch is still exposed on the critical path (a "
            "sync point crept in ahead of the completion barrier, or the "
            "ticks have no host work to hide; docs/KERNEL_PERF.md Layer 7)"
        )
    if pipeline.get("identical_assignments") is False:
        print(
            "perfgate: WARNING pipelined loop diverged from the serial loop "
            "— the overlap must be bit-identical (tests/test_pipeline.py)"
        )
    if pipeline.get("speedup", 0.0) < 1.2:
        print(
            "perfgate: WARNING pipeline speedup below the 1.2x ISSUE-14 "
            "acceptance floor — the overlap is not paying for itself"
        )


def report_watchdog(detail: dict) -> None:
    """Surface the watchdog line: any abandoned (hung) device calls during
    the bench, and the monitored-dispatch overhead on the pipelined warm
    tick.  Advisory: warns when the overhead exceeds 2% of
    ``pipeline_warm_tick_s`` — the wrappers must stay invisible on the hot
    path (docs/KERNEL_PERF.md "Watchdog")."""
    timeouts = detail.get("watchdog_timeouts") or {}
    if timeouts:
        print(
            f"perfgate: WARNING watchdog abandoned hung device calls during "
            f"the bench: {timeouts} — the backend went quiet mid-run "
            f"(bounded by SolveTimeout instead of hanging the bench)"
        )
    overhead = detail.get("pipeline_watchdog_overhead_frac")
    if overhead is None:
        return
    print(
        f"perfgate: watchdog overhead on the pipelined warm tick: "
        f"{overhead * 100:.1f}%"
    )
    if overhead > 0.02:
        print(
            "perfgate: WARNING watchdog overhead above the 2% budget on "
            "pipeline_warm_tick_s — the monitored dispatch/fetch wrappers "
            "are no longer invisible (utils/watchdog.py; KC_WATCHDOG=0 to "
            "A/B locally)"
        )


def report_policy(detail: dict) -> None:
    """Surface the policy-objective line: fleet cost first-fit vs objective
    and the scoring-stage cost.  The fleet-cost delta is the ISSUE-9
    acceptance floor (> 0 on the demo fleet); the enforced stage gate is
    ``objective_s`` in GATED_STAGES."""
    policy = detail.get("policy")
    if not policy:
        return
    if "error" in policy:
        print(f"perfgate: policy bench errored: {policy['error']}")
        return
    print(
        "perfgate: policy fleet cost {p:.4f} vs first-fit {f:.4f} — delta "
        "{d:.4f}, objective_s {o:.4f}s, identical_placements={i}".format(
            p=policy["fleet_cost_policy"], f=policy["fleet_cost_firstfit"],
            d=policy["fleet_cost_delta"], o=policy["objective_s"],
            i=policy.get("identical_placements"),
        )
    )
    if policy.get("fleet_cost_delta", 0.0) <= 0.0:
        print(
            "perfgate: WARNING policy fleet-cost delta is not positive — the "
            "objective stage stopped beating first-fit on the demo fleet "
            "(ISSUE-9 acceptance floor)"
        )
    if not policy.get("identical_placements", True):
        print(
            "perfgate: WARNING policy decode changed pod placements — the "
            "objective stage must select offerings, never reassign pods"
        )


def report_relax(detail: dict) -> None:
    """Surface the relax-vs-scan solver family line (ISSUE-20,
    docs/RELAX.md): both solve walls, the fleet-cost delta, and the audit's
    violation count.  The enforced side is ``relax_solve_s`` in
    GATED_STAGES; advisory warnings fire when the relaxation's fleet costs
    MORE than the greedy scan (the acceptance yardstick is delta >= 0) or
    when the routed mode shows the bench fell back to the scan — the numbers
    then measure the scan twice and gate nothing relax-specific."""
    relax = detail.get("relax")
    if not relax:
        return
    if "error" in relax:
        print(f"perfgate: relax bench errored: {relax['error']}")
        return
    print(
        "perfgate: relax solve {r:.4f}s vs scan {s:.4f}s — fleet cost "
        "{cr:.4f} vs {cs:.4f} (delta {d:.4f}), violations={v} "
        "iters={i} leftover={lo} mode={m}".format(
            r=relax["relax_solve_s"], s=relax["scan_solve_s"],
            cr=relax["fleet_cost_relax"], cs=relax["fleet_cost_scan"],
            d=relax["fleet_cost_delta"], v=relax["rounded_violations"],
            i=relax["relax_iters"], lo=relax["relax_leftover"],
            m=relax.get("relax_mode"),
        )
    )
    if relax.get("relax_mode") != "relax":
        print(
            "perfgate: WARNING relax bench fell back to the scan "
            f"({relax.get('relax_mode')}) — relax_solve_s measured the "
            "greedy kernel, not the relaxation"
        )
    if relax.get("fleet_cost_delta", 0.0) < 0.0:
        print(
            "perfgate: WARNING relax fleet cost is worse than greedy — the "
            "relaxation must match or beat the scan on the skewed bench "
            "fleet (ISSUE-20 acceptance floor, docs/RELAX.md)"
        )


def report_sharded(detail: dict) -> None:
    """Surface the mesh scaling line: per-size solve_s, speedup, efficiency,
    and the bit-parity fact.  The ISSUE-10 acceptance floor is a 1.5x
    best-mesh speedup over 1-device at the 100k-pod / 2k-type fleet (or a
    documented host-fabric cap); the enforced side is the two sharded stage
    durations in GATED_STAGES."""
    sharded = detail.get("sharded")
    if not sharded:
        return
    if "error" in sharded:
        print(f"perfgate: sharded bench errored: {sharded['error']}")
        return
    for rec in sharded.get("sizes", ()):
        if "error" in rec:
            print(f"perfgate: sharded mesh={rec.get('mesh_devices')} "
                  f"errored: {rec['error']}")
            continue
        extra = ""
        if "speedup" in rec:
            extra = (f" speedup {rec['speedup']:.2f}x "
                     f"efficiency {rec['efficiency']:.3f}")
        print(f"perfgate: sharded mesh={rec['mesh_devices']} "
              f"solve_s {rec['solve_s']:.4f}s{extra}")
    if not sharded.get("identical_placements", True):
        print(
            "perfgate: WARNING sharded solve changed placements across mesh "
            "sizes — the shard_map dispatch must stay bit-identical to the "
            "single-device solve"
        )
    speedup = sharded.get("speedup_best")
    if speedup is not None and speedup < 1.5:
        print(
            "perfgate: WARNING sharded best-mesh speedup "
            f"{speedup:.2f}x below the 1.5x ISSUE-10 acceptance floor — "
            "the host fabric (or a regression) is capping catalog sharding"
        )


def report_tenant(detail: dict) -> None:
    """Surface the multi-tenant coalescing line (ISSUE-12, docs/SERVICE.md):
    batched (vmapped tenant axis) vs serial solve throughput over N
    same-bucket tenants, plus the serial path's p99.  Advisory: warns when
    coalescing stops beating serial dispatch."""
    tenant = detail.get("tenant")
    if not tenant:
        return
    if "error" in tenant:
        print(f"perfgate: tenant bench errored: {tenant['error']}")
        return
    print(
        "perfgate: tenant x{n} batched {b:.4f}s ({bt:.1f} solves/s) vs "
        "serial {s:.4f}s ({st:.1f} solves/s) — speedup {x:.2f}x, "
        "p99 serial solve {p:.4f}s, buckets={k}".format(
            n=tenant["tenants"], b=tenant["batched_s"],
            bt=tenant["batched_solves_per_s"], s=tenant["serial_s"],
            st=tenant["serial_solves_per_s"], x=tenant.get("speedup") or 0.0,
            p=tenant["p99_serial_solve_s"], k=tenant.get("shape_buckets"),
        )
    )
    if tenant.get("shape_buckets", 1) != 1:
        print(
            "perfgate: WARNING tenant bench snapshots landed in "
            f"{tenant['shape_buckets']} shape buckets — the coalescer can "
            "only batch within one bucket, so the speedup number is "
            "measuring the wrong regime"
        )
    speedup = tenant.get("speedup")
    if speedup is not None and speedup <= 1.0:
        print(
            "perfgate: WARNING tenant batched solve no faster than serial "
            f"({speedup:.2f}x) — coalescing overhead is eating the "
            "multi-tenant win (docs/SERVICE.md triage)"
        )


def report_fusion(detail: dict) -> None:
    """Surface the generalized solve-fusion line (PR 18, docs/SERVICE.md
    "Solve fusion"): fused vs serial cross-tenant REPAIR dispatch
    throughput at each tenant count, plus the KC_BUCKET_QUANTIZE sweep.
    Advisory: warns when fused repair throughput drops under the 2x floor
    at the deepest count; the enforced side is ``fusion_repair_solve_s``
    in GATED_STAGES."""
    fusion = detail.get("fusion")
    if not fusion:
        return
    if "error" in fusion:
        print(f"perfgate: fusion bench errored: {fusion['error']}")
        return
    for n, row in sorted(
        (fusion.get("repair") or {}).items(), key=lambda kv: int(kv[0])
    ):
        print(
            "perfgate: fusion x{n} repair fused {f:.4f}s vs serial "
            "{s:.4f}s — speedup {x:.2f}x".format(
                n=n, f=row["fused_s"], s=row["serial_s"],
                x=row.get("speedup") or 0.0,
            )
        )
    speedup = fusion.get("fusion_speedup")
    deepest = max(
        (int(n) for n in (fusion.get("repair") or {})), default=0
    )
    if speedup is not None and speedup < 2.0:
        print(
            f"perfgate: WARNING fused repair only {speedup:.2f}x serial at "
            f"{deepest} tenants (< 2x floor) — repair fusion is not paying "
            "for its stacking overhead (docs/SERVICE.md triage: "
            "KC_COALESCE_WINDOW)"
        )
    quant = fusion.get("quantize") or {}
    default, quantized = quant.get("default"), quant.get("quantized")
    if default and quantized:
        print(
            "perfgate: fusion quantize ladder: {bd} buckets -> {bq} "
            "(occupancy {od} -> {oq} tenants/dispatch, padded FLOPs "
            "{fd:.0f} -> {fq:.0f})".format(
                bd=default["buckets"], bq=quantized["buckets"],
                od=default.get("tenants_per_dispatch"),
                oq=quantized.get("tenants_per_dispatch"),
                fd=default.get("padded_flops") or 0.0,
                fq=quantized.get("padded_flops") or 0.0,
            )
        )
        if quantized["buckets"] > default["buckets"]:
            print(
                "perfgate: WARNING the quantized ladder produced MORE "
                "buckets than the default — KC_BUCKET_QUANTIZE stopped "
                "being a subset grid"
            )


def report_fleet(detail: dict) -> None:
    """Surface the fleet failover restore line (ISSUE-17, docs/FLEET.md):
    checkpoint-restore vs journal-replay adoption cost per chain depth.  The
    enforced side is ``fleet_restore_s`` in GATED_STAGES; the advisory warns
    when the tensor checkpoint stops beating replay by ≥5x at the deepest
    chain (64 deltas — the whole point of checkpoints over replay), or when
    the two restored lineages stop answering bit-identically."""
    fleet = detail.get("fleet")
    if not fleet:
        return
    if "error" in fleet:
        print(f"perfgate: fleet bench errored: {fleet['error']}")
        return
    for row in fleet.get("restores", []):
        print(
            "perfgate: fleet restore @{d} deltas: checkpoint {c:.4f}s vs "
            "replay {r:.4f}s — speedup {x:.2f}x, bit_identical={b}".format(
                d=row["deltas"], c=row["checkpoint_restore_s"],
                r=row["replay_restore_s"], x=row.get("speedup") or 0.0,
                b=row.get("bit_identical"),
            )
        )
        if not (row.get("warm_ok") and row.get("replay_ok")):
            print(
                "perfgate: WARNING fleet restore rung failed at "
                f"{row['deltas']} deltas (warm_ok={row.get('warm_ok')}, "
                "replay_ok={0}) — the failover ladder is broken "
                "(docs/FLEET.md triage)".format(row.get("replay_ok"))
            )
        if row.get("bit_identical") is False:
            print(
                "perfgate: WARNING checkpoint-restored and replay-restored "
                f"lineages diverged on the next solve at {row['deltas']} "
                "deltas — a checkpoint plane is drifting from the journal "
                "truth (docs/FLEET.md bit-identity contract)"
            )
    deepest = detail.get("fleet_restore_deltas")
    speedup = detail.get("fleet_restore_speedup")
    if deepest is not None and deepest >= 64 and speedup is not None \
            and speedup < 5.0:
        print(
            "perfgate: WARNING fleet checkpoint restore only "
            f"{speedup:.2f}x faster than journal replay at {deepest} "
            "deltas (< 5x acceptance floor) — the one-deserialize restore "
            "is losing its reason to exist (docs/FLEET.md)"
        )


def report_recovery(detail: dict) -> None:
    """Surface the durable-session journal's hot-path cost (ISSUE-13,
    docs/SERVICE.md): the tenant bench's serial p99 with a per-solve journal
    append vs without.  The append is an enqueue — framing and fsync ride
    the writer thread — so the advisory warns when it adds more than 5% to
    the tenant p99 (something is blocking the RPC path that shouldn't)."""
    tenant = detail.get("tenant")
    if not tenant or "journal_overhead_fraction" not in tenant:
        return
    overhead = tenant.get("journal_overhead_fraction")
    if overhead is None:
        return
    print(
        "perfgate: recovery journal p99 {j:.4f}s vs {p:.4f}s bare — "
        "append overhead {o:+.1f}%".format(
            j=tenant["p99_serial_journal_s"],
            p=tenant["p99_serial_solve_s"],
            o=overhead * 100.0,
        )
    )
    if overhead > 0.05:
        print(
            "perfgate: WARNING journal append adds "
            f"{overhead * 100.0:.1f}% to the tenant p99 (>5%) — the append "
            "path must stay enqueue-only; check KC_JOURNAL_FSYNC discipline "
            "and queue depth (docs/SERVICE.md durable-session triage)"
        )


def report_telemetry(detail: dict) -> None:
    """Surface the fully-enabled telemetry cost (ISSUE-16,
    docs/OBSERVABILITY.md): the pipelined warm tick re-run with tracing ON
    (spans, exemplars, occupancy/overlap gauges all live) against the
    KC_TRACE=0 leg it normally runs as.  Advisory: warns past 2% of
    ``pipeline_warm_tick_s`` — observability must not tax the hot path it
    observes.  Also prints the coalesced batch-occupancy ledger so padding
    waste is visible next to the speedup it buys."""
    overhead = detail.get("pipeline_telemetry_overhead_frac")
    if overhead is not None:
        pipeline = detail.get("pipeline") or {}
        print(
            "perfgate: telemetry-on warm tick {t:.4f}s vs {p:.4f}s traced-off "
            "— overhead {o:.1f}%".format(
                t=pipeline.get("traced_tick_s") or 0.0,
                p=pipeline.get("pipelined_tick_s") or 0.0,
                o=overhead * 100.0,
            )
        )
        if overhead > 0.02:
            print(
                "perfgate: WARNING fully-enabled telemetry adds "
                f"{overhead * 100.0:.1f}% to pipeline_warm_tick_s (>2%) — "
                "span bookkeeping or a gauge update crept inside the timed "
                "loop (tracing must stay one flag check when off; "
                "docs/OBSERVABILITY.md)"
            )
    occupancy = detail.get("batch_occupancy") or {}
    for key, stats in sorted(occupancy.items()):
        print(
            "perfgate: batch occupancy [{k}]: ratio {r:.3f} over "
            "{d} dispatches ({t} tenant-rows, padded_flops {f:.0f})".format(
                k=key, r=stats.get("occupancy_ratio") or 0.0,
                d=stats.get("dispatches"), t=stats.get("tenant_rows"),
                f=stats.get("padded_flops") or 0.0,
            )
        )
        ratio = stats.get("occupancy_ratio")
        if ratio is not None and ratio < 0.5:
            print(
                "perfgate: WARNING coalesced batch occupancy below 0.5 — "
                "more than half the padded rows are dead weight; the bucket "
                "ladder is too coarse for this tenant mix "
                "(docs/SERVICE.md coalescing triage)"
            )


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="allowed fractional drop vs last same-platform, same-machine record")
    ap.add_argument("--cross-machine-tolerance", type=float, default=0.20,
                    help="allowed drop when the last record came from another machine")
    ap.add_argument("--stage-tolerance", type=float, default=0.25,
                    help="allowed fractional increase per stage duration "
                         "(solve_decode_s/ingest_s/cold_s) vs the last record")
    ap.add_argument("--cross-machine-stage-tolerance", type=float, default=0.50,
                    help="per-stage tolerance when the last record came from "
                         "another machine")
    ap.add_argument("--record", default=None,
                    help="also write the fresh bench line to this path")
    args = ap.parse_args()

    analysis_rc = gate_analysis_budget()
    rec = run_bench()
    detail = rec.get("detail") or {}
    platform = detail.get("platform")
    pods_per_sec = detail.get("pods_per_sec")
    warn_compile_budget(detail)
    report_churn(detail)
    report_pipeline(detail)
    report_policy(detail)
    report_relax(detail)
    report_sharded(detail)
    report_tenant(detail)
    report_fusion(detail)
    report_fleet(detail)
    report_recovery(detail)
    report_watchdog(detail)
    report_telemetry(detail)
    if pods_per_sec is None:
        print(json.dumps(rec))
        print("perfgate: FAIL (bench produced no pods_per_sec)")
        return 1
    if args.record:
        with open(args.record, "w") as f:
            json.dump(rec, f)

    prior = last_record(platform)
    if prior is None:
        print(f"perfgate: PASS (no prior {platform} record; "
              f"current {pods_per_sec} pods/s)")
        return analysis_rc
    rnd, path, prev = prior
    prev_detail = prev.get("detail") or {}
    prev_pps = prev_detail["pods_per_sec"]
    same_machine = (
        detail.get("machine") is not None
        and detail.get("machine") == prev_detail.get("machine")
    )
    tol = args.tolerance if same_machine else args.cross_machine_tolerance
    stage_tol = (args.stage_tolerance if same_machine
                 else args.cross_machine_stage_tolerance)
    floor = prev_pps * (1.0 - tol)
    strict = os.environ.get("KC_PERF_GATE_STRICT", "0") == "1"

    stages = compare_stages(detail, prev_detail, stage_tol)
    regressed = [row for row in stages if row[3]]
    for key, cur, prev_v, bad in stages:
        delta = (cur - prev_v) / prev_v if prev_v else 0.0
        flag = " REGRESSED" if bad else ""
        print(f"perfgate: stage {key}: {cur:.4f}s vs {prev_v:.4f}s "
              f"({delta:+.0%}){flag}")

    drifted = pods_per_sec < floor or bool(regressed)
    verdict = "PASS" if not drifted else ("FAIL" if strict else "WARN")
    print(
        f"perfgate: {verdict} — {pods_per_sec} pods/s on {platform} vs "
        f"{prev_pps} in {os.path.basename(path)} (round {rnd}, "
        f"{'same' if same_machine else 'different'} machine, "
        f"tolerance {tol:.0%}, floor {floor:.0f})"
    )
    if regressed:
        names = ", ".join(row[0] for row in regressed)
        print(f"perfgate: stage regression past {stage_tol:.0%}: {names}")
    if verdict == "WARN":
        print("perfgate: advisory mode — drift does not fail presubmit "
              "(KC_PERF_GATE_STRICT=1 to enforce)")
    return 1 if (verdict == "FAIL" or analysis_rc) else 0


if __name__ == "__main__":
    sys.exit(main())
