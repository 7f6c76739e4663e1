"""The reduction from a profiler trace to device numbers
(benchmark/harness/xplane.py): exact arithmetic on a hand-made trace, and the
same code on a small trace recorded on a TPU v5e and checked in beside this
file.  ``v5e_tiny.xplane.pb.gz`` is ``benchmark/run.py --workload
backlog-50k.full --rehearse --trace 1`` on the chip (PR 22, first mix): two
units of 1 400 pods x 24 types.  It was cut to what the reduction reads — the
chip's ``XLA Ops`` and ``XLA Modules`` lines and the benchmark's own
annotations, every event with its recorded time, op names cut to 120
characters, stats dropped — which took it from 5.9 MB to 0.6 MB."""

import gzip
import os

import pytest

from benchmark.harness import xplane
from benchmark.harness.sut import CLIENT, HANDLER, UNIT

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "v5e_tiny.xplane.pb.gz")

# two units of 10 s; in each: client [0,9], handler [2,6], one program [3,5]
# whose ``while`` spans two fusions with a gap between them
HAND = {
    "annotations": {
        UNIT: [(0.0, 10.0), (10.0, 20.0)],
        CLIENT: [(0.0, 9.0), (10.0, 19.0)],
        HANDLER: [(2.0, 6.0), (12.0, 16.0)],
    },
    "chips": [{
        "ops": [
            ("while.1", 3.0, 5.0), ("fusion.1", 3.0, 3.5), ("fusion.2", 4.0, 5.0),
            ("while.1", 13.0, 15.0), ("fusion.1", 13.0, 13.5), ("fusion.2", 14.0, 15.0),
            ("copy.9", 30.0, 31.0),  # outside the traced window: not counted
        ],
        "modules": [("jit_solve", 2.9, 5.0), ("jit_solve", 12.9, 15.0)],
    }],
}


def test_busy_is_the_union_and_idle_the_rest():
    got = xplane.reduce(HAND)
    assert got["window_s"] == pytest.approx(20.0)
    assert got["busy_s"] == pytest.approx(4.0)  # the whiles cover their bodies
    assert got["chips"] == 1 and got["units"] == 2
    assert got["unit_busy_s"] == pytest.approx([2.0, 2.0])
    assert got["unit_program_s"] == pytest.approx([2.1, 2.1])


def test_per_op_sums_are_self_time():
    ops = dict(xplane.reduce(HAND)["device_ops"])
    # a while's own time is what its body leaves uncovered
    assert ops == pytest.approx({"fusion.2": 2.0, "fusion.1": 1.0, "while.1": 1.0})
    assert list(ops) == ["fusion.2", "fusion.1", "while.1"]  # most time first


def test_idle_gaps_are_named_by_what_was_going_on():
    gaps = dict(xplane.reduce(HAND)["idle_gaps"])
    assert gaps == pytest.approx({
        "client.before_handler": 4.0,  # [0,2] twice
        "service.handler": 2.0 * (0.9 + 1.0),  # [2,2.9] + [5,6]
        "device.between_ops": 0.2,  # the program runs [2.9,3] before its first op
        "client.after_handler": 6.0,  # [6,9] twice
        "generator.inside_unit": 2.0,  # [9,10] twice
    })
    assert sum(gaps.values()) == pytest.approx(20.0 - 4.0)


def test_several_chips_average_busy_and_gaps_need_every_chip_idle():
    second = {"ops": [("fusion.1", 4.0, 7.0)], "modules": []}
    got = xplane.reduce({**HAND, "chips": HAND["chips"] + [second]})
    assert got["chips"] == 2
    assert got["busy_s"] == pytest.approx((4.0 + 3.0) / 2)
    # chip 0 is busy [3,5] and [13,15], chip 1 [4,7]: someone is busy for 6 s
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(20.0 - 6.0)


def test_collectives_are_found_by_name():
    chips = [{"ops": [("all-reduce.3", 1.0, 2.0), ("fusion.1", 2.0, 5.0)], "modules": []}]
    got = xplane.reduce({**HAND, "chips": chips})
    assert got["collective_s"] == pytest.approx(1.0)
    assert got["busy_s"] == pytest.approx(4.0)


@pytest.mark.parametrize("trace", [
    {**HAND, "annotations": {UNIT: [], CLIENT: [], HANDLER: []}},  # never annotated
    {**HAND, "chips": []},  # no device plane
    {**HAND, "chips": [{"ops": [("copy.9", 30.0, 31.0)], "modules": []}]},  # idle throughout
])
def test_nothing_to_read_returns_nothing(trace):
    assert xplane.reduce(trace) is None


def test_between_requests_is_what_no_annotation_covers():
    trace = {
        "annotations": {UNIT: [(0.0, 1.0), (2.0, 3.0)], CLIENT: [], HANDLER: []},
        "chips": [{"ops": [("fusion.1", 0.0, 1.0)], "modules": []}],
    }
    assert dict(xplane.reduce(trace)["idle_gaps"]) == pytest.approx(
        {"between_requests": 1.0, "generator.inside_unit": 1.0})


class TestRecordedV5eTrace:
    @pytest.fixture(scope="class")
    def trace(self, tmp_path_factory):
        path = tmp_path_factory.mktemp("trace") / "v5e_tiny.xplane.pb"
        with gzip.open(RECORDED, "rb") as src:
            path.write_bytes(src.read())
        return xplane.load(str(path))

    def test_load_finds_the_chip_and_the_benchmarks_annotations(self, trace):
        assert len(trace["chips"]) == 1
        assert trace["chips"][0]["ops"] and trace["chips"][0]["modules"]
        units = trace["annotations"][UNIT]
        assert len(units) >= 2
        assert len(trace["annotations"][CLIENT]) >= len(units)
        assert len(trace["annotations"][HANDLER]) >= len(units)
        # the device's clock and the host's agree: every op of the traced
        # units runs while some handler is open
        handlers = trace["annotations"][HANDLER]
        inside = [
            any(h0 <= start and end <= h1 for h0, h1 in handlers)
            for _, start, end in trace["chips"][0]["ops"]
            if units[0][0] <= start and end <= units[-1][1]
        ]
        assert inside and sum(inside) / len(inside) > 0.95

    def test_op_names_are_the_instructions_names(self, trace):
        names = {name for name, _, _ in trace["chips"][0]["ops"]}
        assert all(len(n) < 64 and " " not in n and not n.startswith("%") for n in names)
        assert any(n.startswith("fusion") for n in names)
        assert {m[0].split("(")[0] for m in trace["chips"][0]["modules"]} >= {"jit_call"}

    def test_the_numbers_read_on_the_chip(self, trace):
        """What this very trace gave when it was taken (chip run, PR 22)."""
        got = xplane.reduce(trace)
        assert got["chips"] == 1 and got["units"] == 2
        assert got["window_s"] == pytest.approx(0.110718918, rel=1e-6)
        assert got["busy_s"] == pytest.approx(0.011578791, rel=1e-6)
        assert dict(got["idle_gaps"])["service.handler"] == pytest.approx(0.074834533, rel=1e-6)
        assert got["device_ops"][0][0] == "cond.421"
        assert got["collective_s"] == 0

    def test_reduce_adds_up(self, trace):
        got = xplane.reduce(trace)
        assert 0 < got["busy_s"] < got["window_s"]
        assert sum(s for _, s in got["idle_gaps"]) <= got["window_s"] - got["busy_s"] + 1e-9
        assert len(got["unit_busy_s"]) == got["units"] == len(trace["annotations"][UNIT])
        assert sum(got["unit_busy_s"]) == pytest.approx(got["busy_s"], rel=1e-6)
        # a program's span on the device holds its ops
        assert all(p >= b - 1e-9 for p, b in zip(got["unit_program_s"], got["unit_busy_s"]))
        assert 0 < len(got["device_ops"]) <= 10
        assert got["device_ops"] == sorted(got["device_ops"], key=lambda r: -r[1])
