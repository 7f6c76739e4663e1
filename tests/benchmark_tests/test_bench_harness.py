"""The benchmark's yardstick arithmetic, on hand-made inputs: the verdict, the
answer checks, span self time, the roofline, the window's end rule and the
per-layer readers."""

import pytest

from benchmark.harness import checks, loop, manifest, roofline, spans
from benchmark.harness.sut import HANDLER, Call

# -- the verdict: every quiet way off the device --------------------------------

CLEAN = {
    "builds": 2, "plain_jit_runs": 0, "watchdog_timeouts": {},
    "fallback_counters": {"karpenter_tpu_kernel_fallback": {}},
    "breaker_states": {"tenant:bench": "closed"},
    "solve_modes": ["delta", "full", "scan"], "compiles_in_window": 0,
}


def test_verdict_clean():
    assert checks.verdict(CLEAN) == []


@pytest.mark.parametrize("field, value, says", [
    ("builds", 0, "built no executable"),
    ("plain_jit_runs", 1, "plain-jit"),
    ("watchdog_timeouts", {"solve.dispatch": 1}, "watchdog"),
    ("fallback_counters", {"karpenter_tpu_kernel_fallback": {"reason=degraded": 1.0}}, "moved"),
    ("fallback_counters", {"karpenter_degraded_solves_total": {"controller=p": 2.0}}, "moved"),
    ("breaker_states", {"tenant:bench": "open"}, "breaker"),
    ("breaker_states", {"tenant:bench": "half-open"}, "breaker"),
    ("solve_modes", ["host"], "'host'"),
    ("solve_modes", ["degraded"], "'degraded'"),
    ("solve_modes", ["relax-fallback:slots"], "relax-fallback"),
    ("compiles_in_window", 3, "inside the measured window"),
])
def test_verdict_names_each_quiet_way_off_the_device(field, value, says):
    bad = checks.verdict({**CLEAN, field: value})
    assert len(bad) == 1 and says in bad[0]


# -- answers ---------------------------------------------------------------------


def _reply(nodes, failed=(), residual=()):
    return {
        "newNodes": [{"instanceTypes": list(types), "podIndices": list(idx)}
                     for types, idx in nodes],
        "existingAssignments": {}, "failedPodIndices": list(failed),
        "residualPodIndices": list(residual),
    }


def test_accounting_every_pod_once():
    good = _reply([(["a"], [0, 2]), (["a"], [1])])
    assert checks.accounting(good, 3) == []
    assert checks.counts(good) == {"nodes": 2, "scheduled": 3, "failed": 0, "residual": 0}


@pytest.mark.parametrize("reply, n", [
    (_reply([(["a"], [0, 1])]), 3),  # a pod never answered
    (_reply([(["a"], [0, 1]), (["a"], [1, 2])]), 3),  # a pod answered twice
    (_reply([(["a"], [0, 1])], failed=[2]), 3),  # a pod failed
    (_reply([(["a"], [0, 1])], residual=[2]), 3),  # a pod left residual
])
def test_accounting_refuses(reply, n):
    assert checks.accounting(reply, n)


def test_capacity_from_the_api_objects_alone():
    from karpenter_core_tpu.cloudprovider.fake import instance_types
    from karpenter_core_tpu.testing import make_pod

    catalog = instance_types(4)  # 1..4 vcpu, 2..8 Gi, 10..40 pods
    pods = [make_pod(requests={"cpu": "500m", "memory": "256Mi"}) for _ in range(4)]
    big, small = catalog[3].name, catalog[0].name
    fits = _reply([([big], [0, 1, 2, 3])])
    assert checks.capacity(fits, pods, catalog) == []
    # four half-cpu pods do not fit the 1-vcpu type the node also lists
    too_small = _reply([([big, small], [0, 1, 2, 3])])
    assert "smallest listed type" in checks.capacity(too_small, pods, catalog)[0]
    assert checks.capacity(_reply([([], [0])]), pods, catalog)  # no type at all


# -- spans -----------------------------------------------------------------------


def _span(name, start, dur, sid, parent=None):
    return {"name": name, "startWall": start, "durationS": dur,
            "spanId": sid, "parentId": parent}


TREE = [
    _span(HANDLER, 0.0, 10.0, "r"),
    _span("encode", 1.0, 1.0, "e", "r"),
    _span("dispatch", 2.0, 3.0, "d", "r"),
    _span("solve", 4.0, 2.0, "s", "r"),  # overlaps dispatch by 1 s: another thread
    _span("decode", 6.0, 1.0, "c", "r"),
    _span("decode.fetch", 6.2, 0.5, "f", "c"),
]


def test_self_time_is_the_span_less_the_union_of_its_children():
    # children cover [1,2] + [2,6] + [6,7] = 6 s of 10
    assert spans.self_time(TREE, {HANDLER}) == pytest.approx(4.0)
    assert spans.self_time(TREE, {"decode"}) == pytest.approx(0.5)
    assert spans.self_time(TREE, {"decode", "decode.fetch"}) == pytest.approx(1.0)
    assert spans.self_time(TREE, {"absent"}) == 0.0


def test_covered_counts_nested_and_overlapping_spans_once():
    assert spans.covered(TREE, {"dispatch", "solve"}) == pytest.approx(4.0)
    assert spans.covered(TREE, {"decode", "decode.fetch"}) == pytest.approx(1.0)
    assert spans.union_length([(0, 1), (0.5, 2), (3, 4), (3.5, 3.7)]) == pytest.approx(3.0)


# -- roofline --------------------------------------------------------------------

SHAPES = {"passes": 1, "classes": 64, "slots": 8192, "types_per_chip": 1024,
          "resources": 3, "in_bytes": 3_000_000, "carry_bytes": 10_000_000,
          "out_bytes": 2_000_000}


def test_roofline_floor_and_share():
    assert roofline.floor(SHAPES) == 3_000_000 + 10_000_000 + 2_000_000
    peaks = roofline.peaks_for("TPU v5 lite")
    got = roofline.share(SHAPES, 0.030, peaks)
    assert got["percent"] == pytest.approx(100 * (15_000_000 / 819e9) / 0.030)
    # a floor for the problem, not a model of the scan: more passes or classes
    # over the same arrays move it not at all
    assert roofline.share({**SHAPES, "passes": 2, "classes": 128}, 0.030, peaks) == got


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "_source", ""])
def test_an_unknown_device_is_an_error_never_a_default(kind):
    with pytest.raises(KeyError):
        roofline.peaks_for(kind)


# -- the window ------------------------------------------------------------------


class FakeSidecar:
    def drain_calls(self):
        return []


class FakeKind:
    """Units of exactly ``unit_s`` on a clock of its own (``ticking``)."""

    def __init__(self, group, unit_s):
        self.group, self.unit_s, self.now = group, unit_s, 0.0

    def unit(self, i):
        self.now += self.unit_s
        return i

    def settle(self, i, out):
        return 10, []


@pytest.fixture
def ticking(monkeypatch):
    def install(kind):
        monkeypatch.setattr(loop.time, "perf_counter", lambda: kind.now)
        return kind
    return install


@pytest.mark.parametrize("group", [1, 2, 7])
def test_the_window_ends_on_the_cycle_boundary_nearest_its_seconds(group, ticking):
    kind = ticking(FakeKind(group, 0.01))
    units, window_s = loop.run(kind, FakeSidecar(), 0.2)
    assert len(units) % group == 0
    assert abs(window_s - 0.2) <= 0.5 * group * 0.01 + 1e-9
    assert [u.pods for u in units] == [10] * len(units)
    assert [u.wall_s for u in units] == pytest.approx([0.01] * len(units))
    assert units[3 % len(units)].start_s == pytest.approx(0.01 * (3 % len(units)))


@pytest.mark.parametrize("unit_s, cycles", [(0.0100, 2), (0.0101, 2), (0.0099, 2),
                                            (0.0079, 3), (0.0130, 2), (0.0140, 1)])
def test_cycles_that_divide_the_window_evenly_do_not_toss_a_coin(unit_s, cycles, ticking):
    """Cycles of ten units in a window of 0.2 s: a little faster or slower
    than 0.1 s a cycle is still two cycles, never two or three by chance."""
    units, _ = loop.run(ticking(FakeKind(10, unit_s)), FakeSidecar(), 0.2)
    assert len(units) == 10 * cycles


def test_a_window_is_never_empty(ticking):
    units, _ = loop.run(ticking(FakeKind(3, 0.001)), FakeSidecar(), 0.0)
    assert len(units) == 3


@pytest.mark.parametrize("group, captured", [(1, 2), (2, 2), (7, 7), (17, 8)])
def test_a_traced_window_captures_one_cycle_of_two_to_eight_units(group, captured, ticking):
    class Capture(loop.Profiler):
        events = ()

        def before(self, i):
            if i == self.FIRST:
                self.events += (("start", i),)
                self.started = True

        def unit(self):
            return __import__("contextlib").nullcontext()

        def stop(self):
            if self.started and not self.done:
                self.events += ("stop",)
                self.done = True

    kind, capture = ticking(FakeKind(group, 0.001)), Capture("/nonexistent", group)
    units, _ = loop.run(kind, FakeSidecar(), 0.0, capture)
    assert capture.events == (("start", 1), "stop")
    assert capture.last == captured
    # the window lasts until the capture is done, then to a cycle boundary
    assert len(units) % group == 0 and len(units) > captured


# -- per-layer readers -----------------------------------------------------------


def _unit(client_s, handler_s, tree=()):
    call = Call(client_s, handler_s, 2_000_000, tuple(tree), None)
    return loop.Unit(0.0, client_s, 10, (call,), ())


FACTS = {
    "units": [_unit(11.0, 10.0, TREE), _unit(13.0, 10.0, TREE), _unit(12.0, 10.0, TREE)],
    "counters": {"compiles_in_window": 0, "first_request_s": 9.5},
    "device": {"busy_s": 2.0, "collective_s": 0.5, "unit_busy_s": [0.03, 0.05, 0.04],
               "unit_program_s": [0.0, 0.0, 0.0]},
    "peaks": roofline.peaks_for("TPU v5 lite"),
    "kernel_shapes": SHAPES,
}


@pytest.mark.parametrize("reader, want", [
    ({"kind": "calls", "field": "outside_s"}, 2.0),
    ({"kind": "calls", "field": "reply_mb"}, 2.0),
    ({"kind": "span_self", "of": [HANDLER]}, 4.0),
    ({"kind": "span_self", "of": ["solve.tenant"]}, None),  # nothing to read
    ({"kind": "span_total", "match": ["dispatch"]}, 3.0),
    ({"kind": "span_total", "match": ["journal.checkpoint"]}, None),
    ({"kind": "counter", "name": "first_request_s"}, 9.5),
    ({"kind": "counter", "name": "compiles_in_window"}, 0),
    ({"kind": "counter", "name": "absent"}, None),
    ({"kind": "device_ops", "per_unit": "unit_busy_s"}, 0.04),
    ({"kind": "device_ops", "per_unit": "unit_program_s"}, None),
    ({"kind": "device_ops", "share": "collective_s", "of": "busy_s"}, 25.0),
    ({"kind": "roofline", "per_unit": "unit_busy_s"},
     100 * (roofline.floor(SHAPES) / 819e9) / 0.04),
])
def test_reader(reader, want):
    got = manifest.load_source(reader["kind"])(reader, FACTS)
    assert got == (pytest.approx(want) if want is not None else None)


@pytest.mark.parametrize("kind", ["device_ops", "roofline"])
def test_a_device_reader_without_a_trace_returns_nothing(kind):
    reader = {"kind": kind, "per_unit": "unit_busy_s"}
    assert manifest.load_source(kind)(reader, {**FACTS, "device": None}) is None
