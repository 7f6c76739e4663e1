"""Tracing subsystem: span nesting/ordering, ring-buffer eviction, the Chrome
exporter, the /debug/traces endpoint, decision audits, histogram bucket
exposition with exemplars, and the six stage spans a
kernel solve produces (docs/OBSERVABILITY.md is the contract under test)."""

import json
import urllib.request

import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.metrics.registry import Histogram, Registry


class TestSpans:
    def test_nesting_ids_and_ordering(self, traced):
        with tracing.span("root", batch=1) as root:
            with tracing.span("stage-a") as a:
                a.event("checkpoint", n=3)
            with tracing.span("stage-b"):
                pass
        assert len(tracing.TRACE_STORE) == 1
        trace = tracing.TRACE_STORE.last(1)[0]
        assert trace.trace_id == root.trace_id
        by_name = {s["name"]: s for s in trace.spans}
        assert set(by_name) == {"root", "stage-a", "stage-b"}
        # children share the trace id and point at the root span
        for child in ("stage-a", "stage-b"):
            assert by_name[child]["traceId"] == root.trace_id
            assert by_name[child]["parentId"] == by_name["root"]["spanId"]
        assert by_name["root"]["parentId"] is None
        # start-time ordering reconstructs the pipeline sequence
        ordered = sorted(trace.spans, key=lambda s: s["startWall"])
        assert [s["name"] for s in ordered] == ["root", "stage-a", "stage-b"]
        # durations nest: the root covers its children
        assert by_name["root"]["durationS"] >= by_name["stage-a"]["durationS"]
        assert by_name["stage-a"]["events"][0] == {
            "name": "checkpoint",
            "wall": by_name["stage-a"]["events"][0]["wall"],
            "attrs": {"n": 3},
        }
        assert by_name["root"]["attrs"] == {"batch": 1}

    def test_disabled_tracing_records_nothing(self):
        assert not tracing.enabled()
        with tracing.span("invisible") as sp:
            sp.event("nope")
            sp.set(x=1)
        assert len(tracing.TRACE_STORE) == 0

    def test_exception_annotates_and_propagates(self, traced):
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("kaput")
        trace = tracing.TRACE_STORE.last(1)[0]
        assert "ValueError: kaput" in trace.spans[0]["attrs"]["error"]

    def test_event_cap_bounds_span_memory(self, traced):
        with tracing.span("flood") as sp:
            for i in range(2 * tracing.MAX_EVENTS_PER_SPAN):
                sp.event("e", i=i)
        trace = tracing.TRACE_STORE.last(1)[0]
        assert len(trace.spans[0]["events"]) == tracing.MAX_EVENTS_PER_SPAN

    def test_traced_decorator_opens_a_span(self, traced):
        @tracing.traced("decorated.op")
        def work():
            return 41 + 1

        assert work() == 42
        assert tracing.TRACE_STORE.last(1)[0].spans[0]["name"] == "decorated.op"


class TestRingBuffer:
    def test_eviction_keeps_the_newest(self, traced):
        tracing.TRACE_STORE.set_capacity(3)
        for i in range(7):
            with tracing.span(f"t{i}"):
                pass
        assert len(tracing.TRACE_STORE) == 3
        assert [t.name for t in tracing.TRACE_STORE.last()] == ["t4", "t5", "t6"]

    def test_find_and_last_n(self, traced):
        ids = []
        for i in range(4):
            with tracing.span(f"t{i}") as sp:
                ids.append(sp.trace_id)
        assert tracing.TRACE_STORE.find(ids[1]).name == "t1"
        assert tracing.TRACE_STORE.find("nonexistent") is None
        assert [t.name for t in tracing.TRACE_STORE.last(2)] == ["t2", "t3"]


class TestExporters:
    def _make_trace(self):
        with tracing.span("root"):
            with tracing.span("child") as c:
                c.event("milestone", detail="x")
        return tracing.TRACE_STORE.last(1)[0]

    def test_chrome_export_shape(self, traced):
        trace = self._make_trace()
        doc = json.loads(json.dumps(tracing.to_chrome([trace])))
        events = doc["traceEvents"]
        complete = [e for e in events if e["ph"] == "X"]
        instants = [e for e in events if e["ph"] == "i"]
        assert {e["name"] for e in complete} == {"root", "child"}
        for event in complete:
            assert event["dur"] >= 0 and event["ts"] > 0
            assert event["pid"] == 1 and event["tid"] == 1
        assert instants[0]["name"] == "milestone"
        # span ids ride along for cross-referencing with /debug/traces
        assert all(e["args"]["traceId"] == trace.trace_id for e in complete)


class TestDebugTracesEndpoint:
    @pytest.fixture()
    def server(self):
        from karpenter_core_tpu.operator.httpserver import OperatorHTTP

        http = OperatorHTTP(metrics_port=0, health_port=0).start()
        yield http
        http.stop()

    def _get(self, port, path):
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as resp:
            return resp.status, resp.headers.get("Content-Type", ""), resp.read().decode()

    def test_serves_last_traces_as_json(self, traced, server):
        for i in range(3):
            with tracing.span(f"solve-{i}"):
                with tracing.span("encode"):
                    pass
        status, ctype, body = self._get(server.metrics_port, "/debug/traces")
        assert status == 200 and ctype.startswith("application/json")
        doc = json.loads(body)
        assert doc["enabled"] is True
        assert [t["name"] for t in doc["traces"]] == ["solve-0", "solve-1", "solve-2"]
        status, _, body = self._get(server.metrics_port, "/debug/traces?n=1")
        assert [t["name"] for t in json.loads(body)["traces"]] == ["solve-2"]

    def test_endpoint_locked_down_when_tracing_off(self, server):
        assert not tracing.enabled()
        with pytest.raises(urllib.error.HTTPError) as excinfo:
            self._get(server.metrics_port, "/debug/traces")
        assert excinfo.value.code == 403

    def test_exemplars_query_is_a_debug_view(self, traced, server):
        from karpenter_core_tpu.metrics.registry import SOLVE_STAGE_DURATION

        SOLVE_STAGE_DURATION.labels("om-stage").observe(
            0.01, exemplar={"trace_id": "deadbeef"}
        )
        status, ctype, body = self._get(server.metrics_port, "/metrics?exemplars=1")
        assert status == 200
        assert '# {trace_id="deadbeef"}' in body
        # the default exposition (the scrape surface) never carries exemplars
        _, ctype_plain, body_plain = self._get(server.metrics_port, "/metrics")
        assert ctype_plain.startswith("text/plain")
        assert "deadbeef" not in body_plain

    def test_chrome_format_and_bad_n(self, traced, server):
        with tracing.span("solve"):
            pass
        status, _, body = self._get(
            server.metrics_port, "/debug/traces?format=chrome"
        )
        assert status == 200
        assert any(e["name"] == "solve" for e in json.loads(body)["traceEvents"])
        with pytest.raises(urllib.error.HTTPError) as excinfo:
            self._get(server.metrics_port, "/debug/traces?n=bogus")
        assert excinfo.value.code == 400

    def test_surfaces_unschedulable_audit(self, traced, server):
        from karpenter_core_tpu.cloudprovider import fake as fake_cp
        from karpenter_core_tpu.operator.kubeclient import KubeClient
        from karpenter_core_tpu.solver.builder import build_scheduler
        from karpenter_core_tpu.testing import make_pod, make_provisioner

        kube = KubeClient()
        kube.create(make_provisioner())
        scheduler = build_scheduler(
            kube, fake_cp.FakeCloudProvider(), cluster=None, pods=[],
            state_nodes=[], daemonset_pods=[],
        )
        results = scheduler.solve([make_pod(requests={"cpu": 10_000})])
        assert results.failed_pods
        _, _, body = self._get(server.metrics_port, "/debug/traces")
        audits = json.loads(body)["audits"]
        assert len(audits) == 1
        assert audits[0]["engine"] == "host"
        assert "resources" in audits[0]["predicates"]
        assert audits[0]["rejections"][0]["candidate"] == "template/default"


class TestTracePropagation:
    """Cross-boundary propagation (docs/OBSERVABILITY.md): wire_context /
    span_remote carry one trace id across a process boundary, and
    TraceStore.tree merges the per-side segments into one tree."""

    def test_wire_context_requires_tracing_and_a_span(self, traced):
        assert tracing.wire_context() is None  # enabled, but no active span
        tracing.disable()
        with tracing.span("off"):
            assert tracing.wire_context() is None
        tracing.enable()
        with tracing.span("on") as sp:
            ctx = tracing.wire_context()
        assert ctx == {"traceId": sp.trace_id, "spanId": sp.span_id}

    def test_span_remote_adopts_the_remote_trace(self, traced):
        # "client side": a local root span whose context goes on the wire
        with tracing.span("client.solve") as client:
            ctx = tracing.wire_context()
        # "server side": a store-root segment under the client's trace id
        with tracing.span_remote("solve.tenant", ctx, tenant="acme") as srv:
            assert srv.trace_id == client.trace_id
            with tracing.span("solve.incremental"):
                pass
        segments = tracing.TRACE_STORE.last()
        assert [t.name for t in segments] == ["client.solve", "solve.tenant"]
        assert segments[0].trace_id == segments[1].trace_id
        server_root = segments[1].spans[-1]
        assert server_root["parentId"] == client.span_id
        assert server_root["attrs"]["tenant"] == "acme"

    def test_span_remote_without_context_is_a_local_root(self, traced):
        with tracing.span_remote("solve.tenant", None, tenant="t") as sp:
            pass
        assert sp.trace_id  # minted locally, still lands in the store
        assert tracing.TRACE_STORE.last(1)[0].spans[0]["parentId"] is None

    def test_span_remote_disabled_is_noop(self):
        assert not tracing.enabled()
        with tracing.span_remote("x", {"traceId": "a", "spanId": "b"}) as sp:
            sp.event("ignored")
        assert len(tracing.TRACE_STORE) == 0

    def test_tree_merges_segments_in_wall_order(self, traced):
        with tracing.span("client.solve") as client:
            ctx = tracing.wire_context()
        with tracing.span_remote("solve.tenant", ctx):
            with tracing.span("solve.coalesced"):
                pass
        tree = tracing.TRACE_STORE.tree(client.trace_id)
        assert tree.trace_id == client.trace_id
        names = [s["name"] for s in tree.spans]
        assert set(names) == {"client.solve", "solve.tenant",
                              "solve.coalesced"}
        assert names[0] == "client.solve"  # earliest segment leads
        starts = [s["startWall"] for s in tree.spans]
        assert starts == sorted(starts)
        assert tracing.TRACE_STORE.tree("missing") is None

    def test_debug_traces_trace_id_query(self, traced):
        from karpenter_core_tpu.operator.httpserver import OperatorHTTP

        with tracing.span("client.solve") as client:
            ctx = tracing.wire_context()
        with tracing.span_remote("solve.tenant", ctx):
            pass
        http = OperatorHTTP(metrics_port=0, health_port=0).start()
        try:
            url = (f"http://127.0.0.1:{http.metrics_port}/debug/traces"
                   f"?trace_id={client.trace_id}")
            with urllib.request.urlopen(url, timeout=5) as resp:
                doc = json.loads(resp.read().decode())
            assert doc["trace"]["traceId"] == client.trace_id
            assert {s["name"] for s in doc["trace"]["spans"]} == {
                "client.solve", "solve.tenant"
            }
            with pytest.raises(urllib.error.HTTPError) as excinfo:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{http.metrics_port}/debug/traces"
                    "?trace_id=feedface", timeout=5,
                )
            assert excinfo.value.code == 404
        finally:
            http.stop()


class TestDecisionAudit:
    def test_predicate_classification(self):
        cases = {
            "did not tolerate gpu=true:NoSchedule": "taints",
            "IP=0.0.0.0 Port=80 Proto=TCP": "host-ports",
            "would exceed node volume limits": "volumes",
            "exceeds node resources": "resources",
            "no instance type satisfied resources {...}": "resources",
            "unsatisfiable topology constraint for pod anti-affinity, key=zone": "affinity",
            "unsatisfiable topology constraint for topology spread, key=zone": "topology",
            "incompatible requirements, key zone": "requirements",
            "": "unknown",
            "something else entirely": "other",
        }
        for err, expected in cases.items():
            assert tracing.classify_rejection(err) == expected, err

    def test_taint_rejection_audited_per_candidate(self, traced):
        from karpenter_core_tpu.apis.objects import Taint
        from karpenter_core_tpu.cloudprovider import fake as fake_cp
        from karpenter_core_tpu.operator.kubeclient import KubeClient
        from karpenter_core_tpu.solver.builder import build_scheduler
        from karpenter_core_tpu.testing import make_pod, make_provisioner

        kube = KubeClient()
        kube.create(
            make_provisioner(taints=[Taint("team", "a", "NoSchedule")])
        )
        scheduler = build_scheduler(
            kube, fake_cp.FakeCloudProvider(), cluster=None, pods=[],
            state_nodes=[], daemonset_pods=[],
        )
        with tracing.span("solve"):
            results = scheduler.solve([make_pod(requests={"cpu": 1})])
        assert results.failed_pods
        (audit,) = tracing.TRACE_STORE.last(1)[0].audits()
        assert audit["predicates"] == ["taints"]

    def test_no_audit_state_accumulates_when_disabled(self):
        from karpenter_core_tpu.cloudprovider import fake as fake_cp
        from karpenter_core_tpu.operator.kubeclient import KubeClient
        from karpenter_core_tpu.solver.builder import build_scheduler
        from karpenter_core_tpu.testing import make_pod, make_provisioner

        kube = KubeClient()
        kube.create(make_provisioner())
        scheduler = build_scheduler(
            kube, fake_cp.FakeCloudProvider(), cluster=None, pods=[],
            state_nodes=[], daemonset_pods=[],
        )
        scheduler.solve([make_pod(requests={"cpu": 10_000})])
        assert scheduler._audit == {}


class TestHistogramExposition:
    def test_cumulative_buckets_with_inf(self):
        registry = Registry()
        hist = registry.histogram("h_test_seconds", "t", buckets=[0.5, 1, 10])
        for value in (0.25, 0.25, 0.75, 4, 48):  # binary-exact: sum renders cleanly
            hist.observe(value)
        rendered = registry.render()
        assert 'h_test_seconds_bucket{le="0.5"} 2.0' in rendered
        assert 'h_test_seconds_bucket{le="1"} 3.0' in rendered
        assert 'h_test_seconds_bucket{le="10"} 4.0' in rendered
        assert 'h_test_seconds_bucket{le="+Inf"} 5.0' in rendered
        assert "h_test_seconds_count 5.0" in rendered
        assert "h_test_seconds_sum 53.25" in rendered

    def test_boundary_value_counts_into_its_le_bucket(self):
        hist = Histogram("h_edge", "t", buckets=[1.0, 2.0])
        hist.observe(1.0)  # le="1" means value <= 1
        samples = {
            (name, labels.get("le")): value for name, labels, value in hist.samples()
        }
        assert samples[("h_edge_bucket", "1")] == 1.0
        assert samples[("h_edge_bucket", "2")] == 1.0
        assert samples[("h_edge_bucket", "+Inf")] == 1.0

    def test_labeled_histogram_buckets_per_child(self):
        registry = Registry()
        hist = registry.histogram("h_lbl", "t", ("stage",), buckets=[1])
        hist.labels("encode").observe(0.5)
        hist.labels("solve").observe(2.0)
        rendered = registry.render()
        assert 'h_lbl_bucket{le="1",stage="encode"} 1.0' in rendered
        assert 'h_lbl_bucket{le="1",stage="solve"} 0.0' in rendered
        assert 'h_lbl_bucket{le="+Inf",stage="solve"} 1.0' in rendered

    def test_exemplars_render_on_request_only(self):
        registry = Registry()
        hist = registry.histogram("h_ex", "t", buckets=[1])
        hist.observe(0.5, exemplar={"trace_id": "abc123"})
        plain = registry.render()
        assert "abc123" not in plain
        with_ex = registry.render(exemplars=True)
        assert '# {trace_id="abc123"} 0.5' in with_ex

    def test_span_close_feeds_stage_histogram_with_trace_exemplar(self, traced):
        from karpenter_core_tpu.metrics.registry import SOLVE_STAGE_DURATION

        with tracing.span("exemplar-stage") as sp:
            trace_id = sp.trace_id
        child = SOLVE_STAGE_DURATION.labels("exemplar-stage")
        assert child.count >= 1
        exemplars = {ex[0]["trace_id"] for ex in child.exemplars.values()}
        assert trace_id in exemplars


class TestSolvePipelineSpans:
    def test_small_solve_produces_the_six_stage_spans(self, traced):
        from karpenter_core_tpu.cloudprovider import fake as fake_cp
        from karpenter_core_tpu.models.columnar import PodIngest
        from karpenter_core_tpu.ops import solve as solve_ops
        from karpenter_core_tpu.solver.tpu import TPUSolver
        from karpenter_core_tpu.testing import make_pod, make_provisioner

        solver = TPUSolver(fake_cp.FakeCloudProvider(), [make_provisioner()])
        pods = [make_pod(requests={"cpu": "500m"}) for _ in range(8)]
        with tracing.span("test.solve"):
            ingest = PodIngest()
            ingest.add_all(pods)
            snapshot = solver.encode(ingest)
            out = solve_ops.solve(snapshot)
            results = solver.decode(snapshot, out)
            assert results.new_nodes
            results.new_nodes[0].instance_type_names  # noqa: B018 - materialize
        trace = tracing.TRACE_STORE.last(1)[0]
        names = {s["name"] for s in trace.spans}
        assert {"ingest", "encode", "dispatch", "solve", "decode",
                "decode.fetch", "materialize"} <= names
        stages = trace.stage_durations()
        assert all(stages[n] >= 0 for n in ("ingest", "encode", "solve", "decode"))
        # the fetch child never exceeds its decode parent (the split is real)
        assert stages["decode.fetch"] <= stages["decode"] + 1e-6
        # every span belongs to the one trace rooted at test.solve
        assert {s["traceId"] for s in trace.spans} == {trace.trace_id}
