"""The readers of the program's spans in the profiler capture
(``harness/annotations.py``, ``sources/annotation_total.py``,
``sources/idle_unspanned.py``): exact arithmetic on a hand-made structure,
then the same code on the capture of one traced rehearsal run from a COPY of
the benchmark — a traced run rewrites ``<ROOT>/.kc_cache/bench_trace``, and
the repo's own is the other rehearsal tests'."""

import json
import os
import shutil

import pytest
from bench_rehearsal import REPO, assert_rehearsal, last_line, run_cell

from benchmark.harness import annotations, manifest
from benchmark.harness.annotations import Span
from benchmark.harness.sources import annotation_total, idle_unspanned

# one unit [0, 10].  Client thread: pack [0,1], rpc [1,8], unpack [8,9].
# Server thread: the handler [2,7] holds decode [2,4] (materialize [3,4] in
# it), solve.tenant [4,6] (session.diff [4,4.5], dispatch [4.5,5.5] in it) and
# pack [6.5,7].  The chip runs one program [4.4,5.6], its ops [4.6,5.4].
HAND = [
    Span("client.pack", 0.0, 1.0, "client"),
    Span("client.rpc", 1.0, 8.0, "client"),
    Span("client.unpack", 8.0, 9.0, "client"),
    Span("service.solve_classes", 2.0, 7.0, "server"),
    Span("service.decode", 2.0, 4.0, "server"),
    Span("service.materialize", 3.0, 4.0, "server"),
    Span("solve.tenant", 4.0, 6.0, "server"),
    Span("session.diff", 4.0, 4.5, "server"),
    Span("dispatch", 4.5, 5.5, "server"),
    Span("service.pack", 6.5, 7.0, "server"),
]
UNIT = (0.0, 10.0)


def _seconds(match, less=(), self_time=False, window=UNIT, spans=HAND):
    return annotation_total.seconds(spans, window, set(match), set(less), self_time)


@pytest.mark.parametrize("match, less, self_time, want", [
    (["client.pack"], (), False, 1.0),
    (["client.rpc"], ["service.solve_classes"], False, 2.0),  # the hop: 7 - 5
    (["service.decode"], ["service.materialize"], False, 1.0),
    # self: the handler less decode, solve.tenant and pack inside it; the
    # client's rpc AROUND it is not inside and must not cancel it
    (["service.solve_classes"], (), True, 5.0 - 2.0 - 2.0 - 0.5),
    # nested matches count once; their insides are session.diff and dispatch
    (["solve.tenant", "solve.incremental"], (), True, 2.0 - 0.5 - 1.0),
    (["client.pack", "client.unpack"], (), False, 2.0),
    (["client.classify"], (), False, None),  # no such span: nothing
])
def test_annotation_total_arithmetic(match, less, self_time, want):
    got = _seconds(match, less, self_time)
    assert got is None if want is None else got == pytest.approx(want)


def test_annotation_total_clips_to_the_unit_and_unions_across_threads():
    assert _seconds(["client.rpc"], window=(0.0, 2.0)) == pytest.approx(1.0)
    assert _seconds(["client.rpc"], window=(9.0, 10.0)) is None
    both = HAND + [Span("client.pack", 0.5, 1.5, "another-thread")]
    assert _seconds(["client.pack"], spans=both) == pytest.approx(1.5)
    # a span that overlaps a matched one without lying inside it is kept out
    # of ``self``: only contained spans are the matched code's own
    astride = HAND + [Span("journal.checkpoint", 6.0, 7.5, "writer")]
    assert _seconds(["service.solve_classes"], self_time=True, spans=astride) == (
        pytest.approx(0.5))


def test_annotation_total_is_the_median_over_units_that_hold_a_match():
    later = [Span(s.name, s.start_s + 10.0, s.end_s + 10.0 + (s.name == "client.pack"), s.thread)
             for s in HAND if s.name != "service.pack"]
    capture = {"spans": HAND + later, "units": [UNIT, (10.0, 20.0), (20.0, 30.0)]}
    assert annotation_total.per_unit({"match": ["client.pack"]}, capture) == (
        pytest.approx([1.0, 2.0]))
    assert annotation_total.per_unit({"match": ["service.pack"]}, capture) == (
        pytest.approx([0.5]))


def test_idle_is_charged_to_the_innermost_span_on_any_thread():
    got = idle_unspanned.idle_by_span(
        HAND, UNIT, busy=[(4.6, 5.4)], programs=[(4.4, 5.6)])
    assert got == pytest.approx({
        "client.pack": 1.0,
        "client.rpc": 1.0 + 1.0,  # [1,2] before the handler, [7,8] after it
        "service.decode": 1.0,  # [2,3]; the copies inside it take [3,4]
        "service.materialize": 1.0,
        "session.diff": 0.4,  # [4,4.4], until the program starts
        "solve.tenant": 0.4,  # [5.6,6]: the dispatch span ended at 5.5
        "service.solve_classes": 0.5,  # [6,6.5], under no phase
        "service.pack": 0.5,
        "client.unpack": 1.0,
        "unspanned": 1.0,  # [9,10]: the generator's own
    })
    # all of the window outside the program, and nothing of the idle inside it
    assert sum(got.values()) == pytest.approx(10.0 - 1.2)


def test_idle_with_no_busy_chip_or_no_unit_reports_nothing():
    assert idle_unspanned.table({"spans": HAND, "units": [], "busy": [(1, 2)],
                                 "programs": []}) == {}
    assert idle_unspanned.table({"spans": HAND, "units": [UNIT], "busy": [],
                                 "programs": []}) == {}


@pytest.mark.parametrize("kind, spec", [
    (annotation_total, {"match": ["client.pack"]}),
    (idle_unspanned, {}),
])
def test_both_kinds_report_nothing_in_a_rehearsal(kind, spec, monkeypatch, capsys):
    def never():
        raise AssertionError("a rehearsal must not even look for a capture")

    monkeypatch.setattr(annotations, "capture", never)
    assert kind.read(spec, {"peaks": None}) is None
    assert capsys.readouterr().out == ""


def test_a_program_without_annotations_reports_nothing(monkeypatch, capsys):
    """The parent commit's captures hold no ``kc:`` event: every new metric
    is left out of its line, and nothing raises."""
    bare = {"spans": [], "units": [UNIT], "busy": [(4.6, 5.4)], "programs": []}
    monkeypatch.setattr(annotations, "capture", lambda: bare)
    peaks = {"peaks": {"hbm_bytes_per_s": 1.0}}
    assert annotation_total.read({"match": ["client.pack"]}, peaks) is None
    assert idle_unspanned.read({}, peaks) is None
    monkeypatch.setattr(annotations, "capture", lambda: None)
    assert annotation_total.read({"match": ["client.pack"]}, peaks) is None
    assert idle_unspanned.read({}, peaks) is None
    assert capsys.readouterr().out == ""


def test_every_new_metric_names_a_reader_that_exists_and_spans_the_program_opens():
    """Each new ``layer_metrics`` file reads span names that the program's
    source really holds: a renamed span would otherwise read as nothing."""
    import re

    sources = ""
    for path in ("service/snapshot_channel.py", "solver/incremental.py", "service/tenant.py"):
        with open(os.path.join(REPO, "karpenter_core_tpu", path)) as f:
            sources += f.read()
    opened = set(re.findall(r'(?:span|span_remote|traced)\(\s*"([\w.]+)"', sources))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    read = {}
    for m in per_layer:
        with open(os.path.join(manifest.BENCH_DIR, "layer_metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["kind"] in ("annotation_total", "idle_unspanned"):
            assert m["source"] == "program_span" and callable(manifest.load_source(spec["kind"]))
            read[m["name"]] = spec
    assert len(read) == 15
    for name, spec in read.items():
        for span in spec.get("match", []) + spec.get("less", []):
            assert span in opened, (name, span)


# -- the same code on a real capture ------------------------------------------

FAST = {"KC_TENANT_RATE": "100000", "KC_TENANT_BURST": "100000"}


@pytest.fixture(scope="module")
def churn_capture(tmp_path_factory):
    """One traced churn rehearsal from a copy of the benchmark: its last
    line, and its capture as ``annotations.load`` reads it."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(os.path.join(REPO, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    proc = run_cell("backlog-50k.churn", "--trace", "1", "--rehearse",
                    root=str(root), PYTHONPATH=REPO, **FAST)
    result = last_line(proc)
    path = annotations.newest(str(root / ".kc_cache" / "bench_trace"))
    assert path, "the traced rehearsal left no capture"
    return result, proc.stdout, annotations.load(path)


def test_a_rehearsal_reports_none_of_the_new_metrics(churn_capture):
    result, stdout, _ = churn_capture
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "session_self_s", "encode_s", "dispatch_s", "compiles_in_window",
        "first_request_s", "backend_compiles", "device_wait_s", "kernel_device_s",
        "decode_s", "fetch_s",
    }, traced=True)
    assert "idle_by_span" not in stdout


def test_the_capture_holds_the_programs_spans_on_two_threads(churn_capture):
    _, _, capture = churn_capture
    names = {s.name for s in capture["spans"]}
    assert {"client.pack", "client.rpc", "client.unpack", "service.solve_classes",
            "service.decode", "service.materialize", "service.payload", "service.pack",
            "solve.tenant", "session.diff", "solve.incremental", "session.plan",
            "session.adopt", "dispatch", "solve", "decode"} <= names
    assert len(capture["units"]) == 8 and capture["busy"]
    thread = {s.name: s.thread for s in capture["spans"]}
    assert thread["client.rpc"] != thread["service.solve_classes"]
    assert thread["service.decode"] == thread["service.solve_classes"]


def test_every_new_metric_of_the_churn_cell_reads_from_the_capture(
        churn_capture, monkeypatch, capsys):
    _, _, capture = churn_capture
    monkeypatch.setattr(annotations, "capture", lambda: capture)
    cell = manifest.load_cell("backlog-50k.churn")
    facts = {"peaks": {"stand-in": True}}  # anything but a rehearsal's None
    got = {}
    for m in cell.per_layer:
        if m["reader"]["kind"] in ("annotation_total", "idle_unspanned"):
            got[m["name"]] = manifest.load_source(m["reader"]["kind"])(m["reader"], facts)
    assert set(got) == {
        "client_pack_s", "client_hop_s", "client_unpack_s", "service_decode_s",
        "service_materialize_s", "service_payload_s", "service_pack_s",
        "service_unspanned_s", "session_diff_s", "session_plan_s", "session_adopt_s",
        "session_unspanned_s", "idle_unspanned_share",
    }
    for name, value in got.items():
        assert value is not None and value > 0, name
    assert got["idle_unspanned_share"] < 100.0
    table = json.loads(capsys.readouterr().out.splitlines()[-1])["idle_by_span"]
    assert 0 < len(table) <= 15 and all(len(row) == 2 for row in table)
    assert [s for _, s in table] == sorted((s for _, s in table), reverse=True)


def test_the_sums_hold_on_the_capture(churn_capture):
    _, _, capture = churn_capture

    def per_unit(match, **spec):
        return annotation_total.per_unit({"match": match, **spec}, capture)

    handler = per_unit(["service.solve_classes"])
    rpc = per_unit(["client.rpc"])
    hop = per_unit(["client.rpc"], less=["service.solve_classes"])
    assert len(handler) == len(rpc) == len(hop) == 8
    for h, r, d in zip(handler, rpc, hop):
        assert d > 0 and h + d == pytest.approx(r)
    # decode splits into the copies and the rest
    whole = per_unit(["service.decode"])
    rest = per_unit(["service.decode"], less=["service.materialize"])
    copies = per_unit(["service.materialize"])
    for w, r, c in zip(whole, rest, copies):
        assert r + c == pytest.approx(w)
    # the handler's self time and its phases never exceed the handler
    mine = per_unit(["service.solve_classes"], self=True)
    phases = per_unit(["service.decode", "service.payload", "service.pack", "solve.tenant"])
    for h, m, p in zip(handler, mine, phases):
        assert 0 < m and m + p <= h * (1 + 1e-9)
    # every idle second of the window is charged once
    by_span = idle_unspanned.table(capture)
    window = (capture["units"][0][0], capture["units"][-1][1])
    from benchmark.harness import xplane

    occupied = xplane._merge(xplane._clip(capture["busy"] + capture["programs"], window))
    assert sum(by_span.values()) == pytest.approx(
        (window[1] - window[0]) - xplane._length(occupied))
