"""Registry cardinality guard and classic-exposition validity under tenant
churn (ISSUE 16, docs/OBSERVABILITY.md "Label-cardinality guard"): 10k
distinct tenant ids must leave /metrics with a bounded series count, a live
``tenant="_other"`` overflow bucket, and an exposition that still parses —
including label values carrying backslash, double-quote, and newline."""

import re

import pytest

from karpenter_core_tpu.metrics.registry import (
    LabelCardinalityGuard,
    Registry,
    TENANT_LABEL_GUARD,
    tenant_label,
)

# one classic-exposition sample line: name{labels} value — the labels blob
# must contain no RAW newline (escaping is what keeps it one line)
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{([a-zA-Z_][a-zA-Z0-9_]*="
    r'"(?:[^"\\\n]|\\.)*"(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*)?\})?'
    r" [^ \n]+$"
)


def _assert_valid_exposition(text: str) -> None:
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            assert line.startswith(("# HELP ", "# TYPE ")), line
            continue
        assert _SAMPLE.match(line), f"unparseable sample line: {line!r}"


class TestLabelCardinalityGuard:
    def test_10k_tenant_churn_stays_bounded(self):
        registry = Registry()
        guard = LabelCardinalityGuard(cap=16)
        admitted = registry.counter(
            "karpenter_test_admitted_total", "t", ("tenant",)
        )
        latency = registry.histogram(
            "karpenter_test_latency_seconds", "t", ("tenant",), buckets=[1]
        )
        for i in range(10_000):
            label = guard.admit(f"tenant-{i}")
            admitted.labels(label).inc()
            latency.labels(label).observe(0.01)
        # series count: <= (cap + overflow) per family, for the process
        # lifetime — churn cannot re-admit
        assert guard.seen() == 16
        assert guard.overflowed == 10_000 - 16
        assert registry.label_set_count() <= 2 * (16 + 1)
        # the overflow bucket absorbed everyone past the cap
        rendered = registry.render()
        assert 'karpenter_test_admitted_total{tenant="_other"} 9984' in rendered
        assert 'karpenter_test_admitted_total{tenant="tenant-0"} 1' in rendered
        _assert_valid_exposition(rendered)
        # a second churn wave maps straight to overflow, no growth
        before = registry.label_set_count()
        for i in range(10_000, 10_100):
            admitted.labels(guard.admit(f"tenant-{i}")).inc()
        assert registry.label_set_count() == before

    def test_early_tenants_keep_verbatim_series(self):
        guard = LabelCardinalityGuard(cap=2)
        assert guard.admit("a") == "a"
        assert guard.admit("b") == "b"
        assert guard.admit("c") == "_other"
        assert guard.admit("a") == "a"  # already-seen stays verbatim forever
        assert guard.cap == 2

    def test_tenant_label_routes_through_process_guard(self):
        # the module-level helper every {tenant=...} call site uses; restore
        # the guard afterwards so admission here doesn't eat other tests' cap
        cap = TENANT_LABEL_GUARD.cap
        try:
            label = tenant_label("cardinality-test-tenant")
            assert label in ("cardinality-test-tenant",
                             LabelCardinalityGuard.OVERFLOW)
            assert tenant_label("cardinality-test-tenant") == label
        finally:
            TENANT_LABEL_GUARD.reset(cap)


class TestLabelValueEscaping:
    @pytest.mark.parametrize("value,expected", [
        ('quo"ted', 'tenant="quo\\"ted"'),
        ("back\\slash", 'tenant="back\\\\slash"'),
        ("new\nline", 'tenant="new\\nline"'),
        ('all\\of"them\n', 'tenant="all\\\\of\\"them\\n"'),
    ])
    def test_special_characters_render_escaped(self, value, expected):
        registry = Registry()
        counter = registry.counter("karpenter_test_esc_total", "t", ("tenant",))
        counter.labels(value).inc()
        rendered = registry.render()
        assert expected in rendered
        _assert_valid_exposition(rendered)

    def test_newline_value_cannot_break_a_sample_line(self):
        registry = Registry()
        gauge = registry.gauge("karpenter_test_nl", "t", ("tenant",))
        gauge.labels('evil\n} 1\nother_metric{x="y').set(3)
        rendered = registry.render()
        # exactly one sample line for the family, newline neutralized
        samples = [ln for ln in rendered.splitlines()
                   if ln.startswith("karpenter_test_nl{")]
        assert len(samples) == 1
        assert "\\n" in samples[0]
        _assert_valid_exposition(rendered)

    def test_histogram_exemplar_labels_escape_too(self):
        registry = Registry()
        hist = registry.histogram("karpenter_test_ex_seconds", "t",
                                  buckets=[1])
        hist.observe(0.5, exemplar={"trace_id": 'x"y\nz'})
        rendered = registry.render(exemplars=True)
        for line in rendered.splitlines():
            assert "\n" not in line  # splitlines guarantees it; belt and
        assert '\\"y\\nz' in rendered


class TestBatchOccupancyLedger:
    """The coalescer's real-vs-padded accounting (utils.compilecache):
    `record_batch_occupancy` is called once per device dispatch and must
    (a) keep a cumulative per-(bucket, mesh) ledger
    (`occupancy_stats`), and (b) publish the live gauge/counter pair
    `karpenter_batch_occupancy_ratio` / `karpenter_padded_flops_total`."""

    @pytest.fixture(autouse=True)
    def fresh_ledger(self):
        from karpenter_core_tpu.utils import compilecache

        compilecache.reset_occupancy()
        yield
        compilecache.reset_occupancy()

    def test_ledger_accumulates_per_bucket_and_mesh(self):
        from karpenter_core_tpu.utils import compilecache

        # two dispatches into the 16-row bucket: 12 and 8 real rows
        compilecache.record_batch_occupancy(12, 16, n_slots=4)
        compilecache.record_batch_occupancy(8, 16, n_slots=4)
        # a sharded dispatch lands in its own (bucket, mesh) cell
        compilecache.record_batch_occupancy(3, 16, n_slots=4,
                                            mesh_axes=("data", 2))
        stats = compilecache.occupancy_stats()
        assert set(stats) == {"16|none", "16|('data', 2)"}
        cell = stats["16|none"]
        assert cell["dispatches"] == 2
        assert cell["real_rows"] == pytest.approx(20.0)
        assert cell["padded_rows"] == 32
        assert cell["occupancy_ratio"] == pytest.approx(20.0 / 32.0)
        # wasted rows x slots x passes: (4 + 8) * 4
        assert cell["padded_flops"] == pytest.approx(48.0)

    def test_coalesced_batch_scales_by_tenants(self):
        from karpenter_core_tpu.utils import compilecache

        # a 3-tenant coalesced dispatch reports the MEAN real rows per
        # batch element; the ledger scales rows by the tenant count
        compilecache.record_batch_occupancy(10.0, 16, n_slots=2, tenants=3)
        cell = compilecache.occupancy_stats()["16|none"]
        assert cell["tenant_rows"] == 3
        assert cell["real_rows"] == pytest.approx(30.0)
        assert cell["padded_rows"] == 48
        assert cell["padded_flops"] == pytest.approx((16 - 10.0) * 2 * 3)

    def test_gauges_reach_the_process_registry(self):
        from karpenter_core_tpu.metrics.registry import REGISTRY
        from karpenter_core_tpu.utils import compilecache

        compilecache.record_batch_occupancy(8, 32, n_slots=1)
        rendered = REGISTRY.render()
        assert ('karpenter_batch_occupancy_ratio'
                '{bucket="32",mesh="none"} 0.25') in rendered
        assert 'karpenter_padded_flops_total{bucket="32",mesh="none"}' \
            in rendered
        _assert_valid_exposition(rendered)
