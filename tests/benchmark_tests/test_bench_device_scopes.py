"""The reader of device time by named scope
(``benchmark/harness/sources/device_scopes.py``) on a hand-made capture: a
real ``.xplane.pb`` written here field by field into ``tmp_path`` (never
``<ROOT>/.kc_cache/bench_trace``, which the traced rehearsal tests of six
workers share), so the module's own wire decoder is under test beside its
arithmetic, and ``jax.profiler`` reads the same file for the units.

One unit [0, 10] s, a second [10, 20] s that only chip 0 works in.  Chip 0,
unit one — as a v5e names things (PERF.md §3: the probe): the scan's ``while``
and a phase's ``conditional`` carry NO ``op_name``, nor does a copy the
compiler put in:

    while.1        [1, 6]    (nameless)                   self 0.5 -> scan
      fusion.1     [1, 2]    kc.scan/../kc.step.derive
      conditional.2 [2, 5]   (nameless)                   self .75 -> scan/phase.plain
        fusion.3   [2.5,3]   ../kc.phase.plain/../kc.existing/kc.fill
        reduce-window.10 [3,3.25] (nameless)              -> scan/phase.plain, NOT its
                                                          neighbours' kc.existing: where
                                                          XLA scheduled it names no block
        fusion.11  [3.25,3.5] ../kc.phase.plain/../kc.existing
        fusion.4   [3.5,4.5] ../kc.phase.plain/../kc.new
        copy.5     [4.5,4.75] (nameless)                  -> scan/phase.plain
      fusion.6     [5, 5.5]  ../kc.scan/../kc.step.record
    fusion.7       [6, 6.5]  kc.finish/add                 a tail-only op_name
    fusion.8       [7, 8]    jit(sweep)/vmap(kc.scan)/../kc.committal
    copy.9         [8, 8.25] (nameless, under nothing)     unscoped
"""

import json
import os
import re

import pytest

from benchmark.harness import annotations, manifest, xplane
from benchmark.harness.sources import device_scopes as scopes
from benchmark.harness.sut import UNIT

PREFIX = "jit(call)/call_exported/jit(f)/"
STEP = PREFIX + "kc.scan/while/body/closed_call/"
PLAIN = STEP + "kc.phase.plain/cond/branch_1_fun/"
SWEPT = "jit(sweep)/vmap(kc.scan)/while/body/closed_call/kc.phase.zone_spread/cond/branch_1_fun/"
# (instruction, op_name, start_s, end_s)
CHIP0 = [
    ("while.1", "", 1.0, 6.0),
    ("fusion.1", STEP + "kc.step.derive/reduce_sum:", 1.0, 2.0),
    ("conditional.2", "", 2.0, 5.0),
    ("fusion.3", PLAIN + "kc.existing/kc.fill/sub:", 2.5, 3.0),
    ("reduce-window.10", "", 3.0, 3.25),
    ("fusion.11", PLAIN + "kc.existing/add:", 3.25, 3.5),
    ("fusion.4", PLAIN + "kc.new/add:", 3.5, 4.5),
    ("copy.5", "", 4.5, 4.75),
    ("fusion.6", STEP + "kc.step.record/add:", 5.0, 5.5),
    ("fusion.7", "kc.finish/add:", 6.0, 6.5),
    ("fusion.8", SWEPT + "kc.committal/mul:", 7.0, 8.0),
    ("copy.9", "", 8.0, 8.25),
    # unit two: the same step once more, shorter
    ("while.1", "", 11.0, 13.0),
    ("fusion.1", STEP + "kc.step.derive/reduce_sum:", 11.0, 12.0),
    ("fusion.6", STEP + "kc.step.record/add:", 12.0, 12.5),
    ("copy.9", "", 30.0, 31.0),  # outside the traced window: not counted
]
CHIP1 = [("fusion.1", STEP + "kc.step.derive/reduce_sum:", 2.0, 4.0)]
UNITS = [(0.0, 10.0), (10.0, 20.0)]

# chip 0's self seconds in unit one, by block
DERIVE, EXISTING, NEW, COMMITTAL, RECORD, OUTSIDE = 1.0, 0.75, 1.0, 1.0, 0.5, 0.5
# the while's own, the conditional's own, the copy and the running sum inside it
GLUE = 0.5 + 0.75 + 0.25 + 0.25
UNSCOPED = 0.25
CONTROL = 0.5 + 0.75
COPY = 0.25 + 0.25  # copy.5 in the glue, copy.9 unscoped: a cut by name, any scope


# -- a .xplane.pb, field by field ----------------------------------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    data = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(data)) + data


def _line(name: str, events, metadata: dict) -> bytes:
    """Events as (metadata name, start_s, end_s); ids handed out by name."""
    body = _field(2, name) + _field(3, 0)  # timestamp_ns 0: offsets are absolute
    for key, start, end in events:
        meta_id = metadata.setdefault(key, len(metadata) + 1)
        body += _field(4, _field(1, meta_id) + _field(2, round(start * 1e12))
                       + _field(3, round((end - start) * 1e12)))
    return _field(3, body)


def _plane(name: str, lines: dict, op_names: dict) -> bytes:
    metadata: dict = {}
    body = _field(2, name)
    for line_name, events in lines.items():
        body += _line(line_name, events, metadata)
    for key, meta_id in metadata.items():
        meta = _field(1, meta_id) + _field(2, key)
        if op_names.get(key):
            meta += _field(5, _field(1, 7) + _field(5, op_names[key]))
        body += _field(4, _field(1, meta_id) + _field(2, meta))
    # stat 7 is the op_name's; 3 is there so that the name is looked up, not assumed
    for stat_id, stat_name in ((3, "flops"), (7, scopes.OP_NAME_STAT)):
        body += _field(5, _field(1, stat_id) + _field(2, _field(1, stat_id) + _field(2, stat_name)))
    return _field(1, body)


def write_capture(directory, chips, scoped: bool = True) -> str:
    """``<directory>/plugins/profile/<run>/hand.xplane.pb``, where
    ``annotations.newest`` looks; an op's event is named by its whole
    instruction, as the chip names it."""
    text = lambda i: f"%{i} = f32[8]{{0}} fusion(f32[8]{{0}} %p.{i}), kind=kLoop"  # noqa: E731
    space = _plane(xplane.HOST_PLANE, {"main": [(UNIT, s, e) for s, e in UNITS]}, {})
    for n, ops in enumerate(chips):
        space += _plane(
            f"/device:TPU:{n}",
            {xplane.OP_LINE: [(text(i), s, e) for i, _, s, e in ops],
             xplane.MODULE_LINE: [("jit_call(1)", 0.5, 9.0)]},
            {text(i): op_name for i, op_name, _, _ in ops} if scoped else {})
    path = os.path.join(directory, "plugins", "profile", "2026_01_01", "hand.xplane.pb")
    os.makedirs(os.path.dirname(path))
    with open(path, "wb") as f:
        f.write(space)
    return path


@pytest.fixture
def capture(tmp_path):
    return scopes.load(write_capture(str(tmp_path), [CHIP0]))


BLOCKS = ("kernel_derive_s", "kernel_existing_s", "kernel_new_s", "kernel_committal_s",
          "kernel_record_s", "kernel_outside_scan_s", "kernel_glue_s")
CUTS = ("kernel_control_s", "kernel_copy_s")


def spec_of(metric: str) -> dict:
    """The reader as the manifest ships it."""
    with open(os.path.join(manifest.BENCH_DIR, "layer_metrics", metric + ".json")) as f:
        return json.load(f)


def test_the_decoder_reads_what_jax_reads(tmp_path):
    path = write_capture(str(tmp_path), [CHIP0, CHIP1])
    assert annotations.newest(str(tmp_path)) == path
    assert annotations.load(path)["units"] == pytest.approx(UNITS)
    theirs = xplane.load(path)["chips"]
    ours = scopes.device_ops(path)
    assert len(ours) == len(theirs) == 2
    for mine, chip in zip(ours, theirs):
        assert sorted((key[1], start, end) for key, start, end in mine) == [
            (name, pytest.approx(start), pytest.approx(end))
            for name, start, end in sorted(chip["ops"])]


def test_events_outside_the_window_go_as_they_are_read(tmp_path):
    path = write_capture(str(tmp_path), [CHIP0])
    everywhere, = scopes.device_ops(path)
    inside, = scopes.device_ops(path, window=(1.5, 20.0))
    gone = sorted(set(everywhere) - set(inside))
    # fusion.1 [1, 2] and while.1 [1, 6] overlap the window and stay
    assert [(key[1], start) for key, start, _ in gone] == [("copy.9", pytest.approx(30.0))]
    assert scopes.self_seconds([inside], (1.5, 20.0)) == scopes.self_seconds([everywhere], (1.5, 20.0))


def test_a_path_is_the_kc_tokens_in_order_whatever_wraps_them():
    assert scopes.scope_path(PLAIN + "kc.existing/kc.fill/cumsum:") == (
        "scan", "phase.plain", "existing", "fill")
    assert scopes.scope_path("jit(f)/kc.sweep.seed/vmap(kc.scan)/iota:") == ("sweep.seed", "scan")
    assert scopes.scope_path("kc.phase.plain/reduce_sum") == ("phase.plain",)  # a tail
    assert scopes.scope_path("jit(f)/reduce_window_sum") == ()


def test_a_nameless_event_takes_the_path_of_what_it_is_nested_with(capture):
    table = capture["units"][0]
    inferred = {(path, i): s for (path, i, inferred), s in table.items() if inferred}
    assert inferred == pytest.approx({
        (("scan",), "while.1"): 0.5,  # the common prefix of all it spans
        (("scan", "phase.plain"), "conditional.2"): 0.75,  # of its branch's ops
        # spanning nothing: the event around them, though reduce-window.10 runs
        # between two ops of kc.existing — the schedule's order is not the program's
        (("scan", "phase.plain"), "copy.5"): 0.25,
        (("scan", "phase.plain"), "reduce-window.10"): 0.25,
    })
    # under nothing and spanning nothing: nothing to infer from
    assert table[((), "copy.9", False)] == pytest.approx(0.25)


@pytest.mark.parametrize("metric, want", [
    ("kernel_derive_s", DERIVE),
    ("kernel_existing_s", EXISTING),
    ("kernel_new_s", NEW),
    ("kernel_committal_s", COMMITTAL),  # under vmap(kc.scan): inside the scan
    ("kernel_record_s", RECORD),
    ("kernel_outside_scan_s", OUTSIDE),  # the tail-only kc.finish
    ("kernel_glue_s", GLUE),
    ("kernel_control_s", CONTROL),
    ("kernel_copy_s", COPY),
])
def test_exact_seconds_per_metric(capture, metric, want):
    spec = spec_of(metric)
    assert spec["kind"] == "device_scopes"
    assert scopes.seconds(spec, capture["units"][0]) == pytest.approx(want)
    # the line's value: the median over the units in which an op ran
    second = {"kernel_derive_s": 1.0, "kernel_record_s": 0.5, "kernel_glue_s": 0.5,
              "kernel_control_s": 0.5}
    assert scopes.value(spec, capture) == pytest.approx((want + second.get(metric, 0.0)) / 2)


def test_the_six_blocks_glue_and_unscoped_are_the_busy_self_time(capture, tmp_path):
    table = capture["units"][0]
    blocks = [scopes.seconds(spec_of(m), table) for m in BLOCKS]
    unscoped = scopes.seconds({"share": "unscoped"}, table)
    assert unscoped == pytest.approx(UNSCOPED)
    busy = xplane.reduce(xplane.load(annotations.newest(str(tmp_path))))["unit_busy_s"][0]
    assert sum(table.values()) == pytest.approx(busy) == pytest.approx(6.75)
    assert sum(blocks) + unscoped == pytest.approx(busy)
    # no key is in two blocks
    for key in table:
        assert sum(scopes.matches(spec_of(m), key) for m in BLOCKS) <= 1, key


def test_the_share_is_of_the_window_and_prints_the_table(capture, capsys):
    share = scopes.value(spec_of("kernel_unscoped_share"), capture)
    assert share == pytest.approx(100.0 * 0.25 / (6.75 + 2.0))
    rows = json.loads(capsys.readouterr().out)["device_by_scope"]
    assert ["scan", pytest.approx(0.5 + 0.5), "while.1"] in rows
    assert ["scan/phase.plain", pytest.approx(1.25), "conditional.2"] in rows
    assert ["scan/phase.zone_spread/committal", pytest.approx(1.0), "fusion.8"] in rows
    assert [scopes.UNSCOPED, pytest.approx(0.25), "copy.9"] in rows
    assert sum(s for _, s, _ in rows) == pytest.approx(8.75)


def test_two_chips_average(tmp_path):
    both = scopes.load(write_capture(str(tmp_path), [CHIP0, CHIP1]))
    table = both["units"][0]
    assert scopes.seconds(spec_of("kernel_derive_s"), table) == pytest.approx((1.0 + 2.0) / 2)
    assert scopes.seconds(spec_of("kernel_new_s"), table) == pytest.approx(1.0 / 2)
    assert sum(table.values()) == pytest.approx((6.75 + 2.0) / 2)


def test_silent_in_a_rehearsal():
    for metric in ("kernel_derive_s", "kernel_unscoped_share"):
        assert scopes.read(spec_of(metric), {"peaks": None}) is None


def test_a_capture_without_scopes_reports_the_share_alone(tmp_path, capsys):
    """A commit before the scopes, or an executable out of a compile cache
    filled before them: nothing is charged to a block, and it is said."""
    bare = scopes.load(write_capture(str(tmp_path), [CHIP0], scoped=False))
    for metric in BLOCKS + CUTS:
        assert scopes.value(spec_of(metric), bare) is None
    assert scopes.value(spec_of("kernel_unscoped_share"), bare) == pytest.approx(100.0)
    said = capsys.readouterr()
    assert json.loads(said.out)["device_by_scope"][0][0] == scopes.UNSCOPED
    assert "carries no kc. scope" in said.err


def test_read_finds_the_newest_capture_and_no_capture_is_nothing(tmp_path, monkeypatch, capsys):
    facts = {"peaks": {"hbm_gb_s": 819.0}}
    monkeypatch.setattr(annotations, "newest", lambda directory=None: None)
    assert scopes.read(spec_of("kernel_new_s"), facts) is None
    path = write_capture(str(tmp_path), [CHIP0])
    monkeypatch.setattr(annotations, "newest", lambda directory=None: path)
    assert scopes.read(spec_of("kernel_new_s"), facts) == pytest.approx(NEW / 2)
    assert scopes.read(spec_of("kernel_unscoped_share"), facts) < 5.0
    assert "device_by_scope" in capsys.readouterr().out


def test_the_tool_prints_the_whole_table(tmp_path, capsys):
    from benchmark.tools import device_scopes as tool

    path = write_capture(str(tmp_path), [CHIP0, CHIP1])
    assert tool.main(["device_scopes.py", path]) == 0
    out = capsys.readouterr().out
    assert "scan/phase.plain/existing/fill" in out and "glue, control" in out
    # seconds, % busy, inferred seconds, of the window's two units on two chips:
    # all of this glue is inferred, nothing of a block
    glue = (GLUE + 0.5) / 2
    assert re.search(rf"\n  glue +{glue:.4f} +[\d.]+ +{glue:.4f}\n", out)
    assert re.search(r"\n  existing +0.3750 +[\d.]+ +0.0000\n", out)
    assert "unscoped, by instruction" in out and "copy.9" in out
