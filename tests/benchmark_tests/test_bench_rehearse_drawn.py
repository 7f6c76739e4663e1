"""The ``suite-drawn-cycle`` traffic (kind ``drawn_cycle``) rehearsed end to
end in a fresh process on the CPU — untraced: traced rehearsals share one
trace directory (PERF.md section 7 (c)) — and the cell as the manifest states
it: the upstream suite's deployment with the suite's own selectors."""

import json

import pytest
from bench_rehearsal import assert_rehearsal, last_line, run_cell

CELL = "suite-400-as-drawn.mixed"
CONTROL = "suite-400.mixed"


def _lines(proc, key: str) -> list:
    return [json.loads(line)[key] for line in proc.stdout.splitlines()
            if line.startswith('{"%s"' % key)]


def test_drawn_rehearsal_untraced():
    proc = run_cell(CELL, "--trace", "0", "--rehearse")
    result = last_line(proc)
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
    assert result["attempted"] % 5 == 0  # whole cycles of the rehearsal's five sizes
    # every guarantee held but the one a rehearsal can never meet
    assert not _lines(proc, "failures")
    (fleets,) = _lines(proc, "fleets")
    assert set(fleets) == {"1", "50", "100", "200", "300"} and fleets["1"] == 1
    totals, fleet = _lines(proc, "oracle_cut")
    assert (totals["pods"], fleet["pods"]) == (100, 300)
    for cut in (totals, fleet):
        assert cut["kernel"]["scheduled"] == cut["host"]["scheduled"] == cut["pods"]
        assert cut["kernel"]["failed"] == cut["host"]["failed"] == 0
    assert 0 < fleet["kernel"]["nodes"] <= fleet["host"]["nodes"]


def test_the_cell_as_the_manifest_states_it():
    from benchmark.harness import manifest

    mine, control = manifest.load_cell(CELL), manifest.load_cell(CONTROL)
    assert mine.chips == 1 and mine.traffic["kind"] == "drawn_cycle"
    assert {m["name"] for m in mine.end_to_end} == {"pods_per_s", "request_p50_s", "setup_s"}
    assert "kernel_host_affinity_s" in {m["name"] for m in mine.per_layer}
    # the control's per-layer metrics, and the one this cell adds
    assert ({m["name"] for m in mine.per_layer} - {m["name"] for m in control.per_layer}
            == {"kernel_host_affinity_s"})
    config, suite = mine.config, control.config
    for key in ("batch_sizes", "types", "provisioners", "chips", "reduced"):
        assert config[key] == suite[key], key
    assert config["oracle"]["pods"] == suite["oracle"]["pods"] == 500
    # the fleet is held one-sided at the largest batch, in a rehearsal too
    assert config["oracle"]["fleet_pods"] == max(config["batch_sizes"]) == 5000
    tiny = manifest.load_cell(CELL, rehearse=True).config
    assert tiny["oracle"]["fleet_pods"] == max(tiny["batch_sizes"]) == 300
    suite_tiny = manifest.load_cell(CONTROL, rehearse=True).config
    assert (tiny["batch_sizes"], tiny["types"]) == (suite_tiny["batch_sizes"], suite_tiny["types"])
    mix, theirs = config["pod_mix"], suite["pod_mix"]
    assert {k: v for k, v in mix.items() if k != "kinds"} == {
        k: v for k, v in theirs.items() if k != "kinds"}
    # makeDiversePods: generic, zone spread, hostname spread, hostname
    # affinity, zone affinity — spread on my-label as drawn, affinity own
    assert [(k["kind"], k.get("topology"), k["label_key"], k.get("selector"))
            for k in mix["kinds"]] == [
        ("generic", None, "my-label", None),
        ("spread", "zone", "my-label", "drawn"),
        ("spread", "hostname", "my-label", "drawn"),
        ("affinity", "hostname", "my-affinity", "own"),
        ("affinity", "zone", "my-affinity", "own"),
    ]
    assert "pod mix: self-affinity" in config["assumed"]
    assert not any("sent as zonal" in k or "its own kind" in k for k in config["assumed"])
    assert any("FEWER, never more" in g for g in config["guarantees"])


@pytest.mark.parametrize("cell", ["backlog-50k.full", "backlog-50k.churn", CONTROL,
                                  "backlog-50k-mesh4.full", "brownfield-5k.full",
                                  "manyshape-50k.full", "consolidate-5k.sweep",
                                  "suite-400.operator"])
def test_the_hostname_affinity_metric_stays_out_of_the_other_cells(cell):
    from benchmark.harness import manifest

    assert "kernel_host_affinity_s" not in {
        m["name"] for m in manifest.load_cell(cell).per_layer}
