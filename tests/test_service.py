"""Snapshot channel (gRPC sidecar), wire codec, and settings store."""

import pytest

from karpenter_core_tpu.apis import codec, labels as labels_api
from karpenter_core_tpu.apis.objects import (
    LabelSelector,
    PodAffinityTerm,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
from karpenter_core_tpu.testing import make_node, make_pod, make_pods, make_provisioner


class TestCodec:
    def test_pod_roundtrip(self):
        pod = make_pod(
            labels={"app": "web"},
            requests={"cpu": 1, "memory": "1Gi"},
            node_selector={labels_api.LABEL_TOPOLOGY_ZONE: "test-zone-1"},
            tolerations=[Toleration(key="k", operator="Exists")],
            topology_spread=[
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                    label_selector=LabelSelector(match_labels={"app": "web"}),
                )
            ],
            pod_anti_affinity=[
                PodAffinityTerm(
                    topology_key=labels_api.LABEL_HOSTNAME,
                    label_selector=LabelSelector(match_labels={"app": "web"}),
                )
            ],
        )
        restored = codec.pod_from_dict(codec.pod_to_dict(pod))
        assert restored.metadata.labels == pod.metadata.labels
        assert restored.spec.node_selector == pod.spec.node_selector
        assert restored.spec.tolerations[0].operator == "Exists"
        assert restored.spec.topology_spread_constraints[0].max_skew == 1
        assert restored.spec.affinity.pod_anti_affinity.required[0].topology_key == (
            labels_api.LABEL_HOSTNAME
        )
        from karpenter_core_tpu.utils import resources as r

        assert r.ceiling(restored) == r.ceiling(pod)

    def test_provisioner_roundtrip(self):
        p = make_provisioner(
            weight=10,
            taints=[Taint("k", "v")],
            limits={"cpu": 100},
            consolidation_enabled=True,
        )
        restored = codec.provisioner_from_dict(codec.provisioner_to_dict(p))
        assert restored.name == p.name
        assert restored.spec.weight == 10
        assert restored.spec.limits.resources == {"cpu": 100.0}
        assert restored.spec.consolidation.enabled

    def test_node_roundtrip(self):
        n = make_node(labels={"a": "b"}, taints=[Taint("t", "v")])
        restored = codec.node_from_dict(codec.node_to_dict(n))
        assert restored.name == n.name
        assert restored.status.allocatable == n.status.allocatable
        assert restored.spec.taints == n.spec.taints


class TestSnapshotChannel:
    @pytest.fixture()
    def channel(self):
        from karpenter_core_tpu.service.snapshot_channel import (
            SnapshotSolverClient,
            serve,
        )

        server, port = serve(FakeCloudProvider())
        client = SnapshotSolverClient(f"127.0.0.1:{port}")
        yield client
        client.close()
        server.stop(0)

    def test_health(self, channel):
        assert channel.health() == {"status": "ok"}

    def test_solve_over_the_wire(self, channel):
        pods = make_pods(5, requests={"cpu": "900m"})
        response = channel.solve(pods, [make_provisioner()])
        placed = sum(len(n["podIndices"]) for n in response["newNodes"])
        assert placed == 5
        assert response["failedPodIndices"] == []
        for node in response["newNodes"]:
            assert node["provisioner"] == "default"
            assert node["instanceTypes"]

    def test_policy_config_threads_through_remote_solve(self):
        """PR 9 leftover regression: a CPU controller replica with the
        policy objective enabled previously fell back SILENTLY to first-fit
        selection on remote solves — PolicyConfig never crossed the wire.
        With the ``policy`` request field, the serving side's objective
        stage must pin the launch to the argmin offering (cheapest first,
        zone pinned) exactly like an in-process policy solve."""
        from karpenter_core_tpu.policy import PolicyConfig
        from karpenter_core_tpu.service.snapshot_channel import (
            SnapshotSolverClient,
            serve,
        )

        provider = FakeCloudProvider()
        its = provider.get_instance_types(None)
        # make a non-first, always-viable catalog entry the unambiguous
        # argmin (arm-instance-type fits any 900m batch; the objective only
        # selects among a node's FEASIBLE cells)
        cheapest = "arm-instance-type"
        for it in its:
            provider.set_price(it.name, 9.0)
        provider.set_price(cheapest, 0.01)

        server, port = serve(provider)
        client = SnapshotSolverClient(f"127.0.0.1:{port}")
        try:
            pods = make_pods(4, requests={"cpu": "900m"})
            with_policy = client.solve_classes(
                pods, [make_provisioner()],
                policy=PolicyConfig(enabled=True),
            )
            without = client.solve_classes(pods, [make_provisioner()])
        finally:
            client.close()
            server.stop(0)

        assert with_policy["newNodes"] and without["newNodes"]
        for node in with_policy["newNodes"]:
            # objective selection: argmin type ordered first, zone pinned
            assert node["instanceTypes"][0] == cheapest
            assert len(node["zones"]) == 1
        # the policy-less request keeps the pre-policy behavior: viability
        # order, nothing pinned (the silent-fallback shape this regression
        # test exists to distinguish)
        assert any(
            node["instanceTypes"][0] != cheapest
            or len(node["zones"]) > 1
            for node in without["newNodes"]
        )

    def test_solve_with_existing_nodes(self, channel):
        node = make_node(
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                labels_api.LABEL_CAPACITY_TYPE: "spot",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                labels_api.LABEL_TOPOLOGY_ZONE: "test-zone-1",
            },
            allocatable={"cpu": 4, "memory": "4Gi", "pods": 10},
        )
        pods = make_pods(2, requests={"cpu": 1})
        response = channel.solve(
            pods,
            [make_provisioner()],
            nodes=[{"node": codec.node_to_dict(node), "pods": []}],
        )
        assigned = response["existingAssignments"]
        assert sum(len(v) for v in assigned.values()) == 2
        assert not response["newNodes"]

    def test_solve_classes_matches_solve(self, channel):
        pods = (
            make_pods(8, requests={"cpu": "900m"})
            + make_pods(4, requests={"cpu": 2, "memory": "2Gi"})
            + [
                make_pod(
                    labels={"app": "s"},
                    requests={"cpu": "250m"},
                    topology_spread=[
                        TopologySpreadConstraint(
                            max_skew=1,
                            topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                            label_selector=LabelSelector(match_labels={"app": "s"}),
                        )
                    ],
                )
                for _ in range(6)
            ]
        )
        full = channel.solve(pods, [make_provisioner()])
        columnar = channel.solve_classes(pods, [make_provisioner()])
        assert sum(len(n["podIndices"]) for n in columnar["newNodes"]) == sum(
            len(n["podIndices"]) for n in full["newNodes"]
        )
        assert len(columnar["newNodes"]) == len(full["newNodes"])
        assert columnar["failedPodIndices"] == []
        # every pod index appears exactly once across nodes
        seen = sorted(
            i for n in columnar["newNodes"] for i in n["podIndices"]
        ) + sorted(columnar["failedPodIndices"])
        assert sorted(seen) == list(range(len(pods)))
        for node in columnar["newNodes"]:
            assert node["instanceTypes"]
            assert node["provisioner"] == "default"

    def test_solve_classes_existing_nodes(self, channel):
        node = make_node(
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                labels_api.LABEL_CAPACITY_TYPE: "spot",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                labels_api.LABEL_TOPOLOGY_ZONE: "test-zone-1",
            },
            allocatable={"cpu": 4, "memory": "4Gi", "pods": 10},
        )
        pods = make_pods(2, requests={"cpu": 1})
        response = channel.solve_classes(
            pods,
            [make_provisioner()],
            nodes=[{"node": codec.node_to_dict(node), "pods": []}],
        )
        assigned = response["existingAssignments"]
        assert sum(len(v) for v in assigned.values()) == 2
        assert not response["newNodes"]

    def test_volume_limits_over_the_wire(self, channel):
        node = make_node(
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                labels_api.LABEL_CAPACITY_TYPE: "spot",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                labels_api.LABEL_TOPOLOGY_ZONE: "test-zone-1",
            },
            allocatable={"cpu": 16, "memory": "16Gi", "pods": 20},
        )
        pods = [
            make_pod(requests={"cpu": "100m"}, pvcs=[f"claim-{i}"]) for i in range(4)
        ]
        response = channel.solve(
            pods,
            [make_provisioner()],
            nodes=[{
                "node": codec.node_to_dict(node),
                "pods": [],
                "volumeLimits": {"csi.test": 2},
            }],
            claim_drivers={f"default/claim-{i}": "csi.test" for i in range(4)},
        )
        placed_existing = sum(len(v) for v in response["existingAssignments"].values())
        placed_new = sum(len(n["podIndices"]) for n in response["newNodes"])
        # attach limit 2 binds over the wire exactly as in-process
        assert placed_existing == 2
        assert placed_new == 2
        assert response["failedPodIndices"] == []

    def test_pvc_pods_without_claim_drivers_stay_unconstrained(self, channel):
        pods = [make_pod(requests={"cpu": "100m"}, pvcs=["c1"])]
        response = channel.solve(pods, [make_provisioner()])
        assert sum(len(n["podIndices"]) for n in response["newNodes"]) == 1

    def test_unsupported_batch_rejected(self, channel):
        import grpc

        from karpenter_core_tpu.apis.objects import ContainerPort

        pod = make_pod()
        pod.spec.containers[0].ports.append(
            ContainerPort(host_port=80, host_ip="10.0.0.1")  # specific-IP: host path
        )
        with pytest.raises(grpc.RpcError) as excinfo:
            channel.solve([pod], [make_provisioner()])
        assert excinfo.value.code() == grpc.StatusCode.FAILED_PRECONDITION


class TestTraceEnvelope:
    """Trace propagation on the tenant wire (ISSUE 16): the OPTIONAL
    ``trace`` envelope field is stamped only while client tracing is on —
    with tracing off the request payload is bit-for-bit what it was before
    trace propagation existed (the hot path pays nothing)."""

    @pytest.fixture()
    def channel(self):
        from karpenter_core_tpu.service.snapshot_channel import (
            SnapshotSolverClient,
            serve,
        )

        server, port = serve(FakeCloudProvider())
        client = SnapshotSolverClient(f"127.0.0.1:{port}")
        yield client
        client.close()
        server.stop(0)

    @pytest.fixture()
    def sent_requests(self, monkeypatch):
        """Capture every request dict the client packs onto the wire."""
        from karpenter_core_tpu.service import snapshot_channel as sc

        captured = []
        real_packb = sc.msgpack.packb

        def spy(obj, *args, **kwargs):
            if isinstance(obj, dict) and "podClasses" in obj:
                captured.append(obj)
            return real_packb(obj, *args, **kwargs)

        monkeypatch.setattr(sc.msgpack, "packb", spy)
        return captured

    def _solve(self, channel):
        return channel.solve_tenant_classes(
            [(make_pod(requests={"cpu": "500m"}), 4)],
            [make_provisioner()],
            tenant={"id": "acme", "sessionVersion": 0},
        )

    def test_tracing_off_sends_no_trace_field(self, channel, sent_requests):
        from karpenter_core_tpu import tracing

        assert not tracing.enabled()
        response = self._solve(channel)
        assert response["tenant"]["id"] == "acme"
        assert sent_requests, "request never crossed the capture point"
        assert "trace" not in sent_requests[-1]["tenant"]

    def test_tracing_on_stamps_callers_span(self, channel, sent_requests):
        from karpenter_core_tpu import tracing

        tracing.TRACE_STORE.clear()
        tracing.enable()
        try:
            with tracing.span("client.solve") as client_span:
                response = self._solve(channel)
        finally:
            tracing.disable()
            tracing.TRACE_STORE.clear()
        assert response["tenant"]["id"] == "acme"
        envelope = sent_requests[-1]["tenant"]
        assert envelope["trace"] == {
            "traceId": client_span.trace_id,
            "spanId": client_span.span_id,
        }

    def test_server_segment_joins_the_client_trace(self, channel):
        from karpenter_core_tpu import tracing

        tracing.TRACE_STORE.clear()
        tracing.enable()
        try:
            with tracing.span("client.solve") as client_span:
                self._solve(channel)
            # in-process gRPC: the serving side shares this TRACE_STORE, so
            # the adopted segment is visible without a /debug/traces fetch
            tree = tracing.TRACE_STORE.tree(client_span.trace_id)
            assert tree is not None
            names = {s["name"] for s in tree.spans}
            assert {"client.solve", "solve.tenant"} <= names
            tenant_span = next(
                s for s in tree.spans if s["name"] == "solve.tenant"
            )
            assert tenant_span["parentId"] == client_span.span_id
            assert tenant_span["attrs"]["tenant"] == "acme"
        finally:
            tracing.disable()
            tracing.TRACE_STORE.clear()


class TestWireSchema:
    """Golden test pinning service/SCHEMA.md to the code: the wire contract
    is stable within karpenter.v1 — field renames must fail here first."""

    def test_pod_wire_fields(self):
        pod = make_pod(
            labels={"a": "b"},
            requests={"cpu": 1},
            host_ports=[80],
            pvcs=["claim-1"],
            topology_spread=[
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                    label_selector=LabelSelector(match_labels={"a": "b"}),
                )
            ],
        )
        d = codec.pod_to_dict(pod)
        assert set(d) == {"metadata", "spec", "status"}
        # metadata carries durability fields since the kubeapi backend
        # (resourceVersion/generation always; deletionTimestamp, finalizers,
        # ownerReferences only when set — absent on this fresh pod)
        assert set(d["metadata"]) == {
            "name", "namespace", "uid", "labels", "annotations", "creationTimestamp",
            "resourceVersion", "generation",
        }
        assert set(d["spec"]) == {
            "nodeSelector", "nodeName", "tolerations", "containers",
            "topologySpreadConstraints", "priority", "priorityClassName", "pvcs",
        }
        container = d["spec"]["containers"][0]
        assert set(container) == {"requests", "limits", "hostPorts"}
        assert set(container["hostPorts"][0]) == {"port", "protocol", "hostIP"}
        spread = d["spec"]["topologySpreadConstraints"][0]
        assert set(spread) == {"maxSkew", "topologyKey", "whenUnsatisfiable", "labelSelector"}
        assert d["spec"]["pvcs"] == ["claim-1"]

    def test_service_method_names(self):
        from karpenter_core_tpu.service.snapshot_channel import SERVICE, SnapshotSolverService

        assert SERVICE == "karpenter.v1.SnapshotSolver"
        service = SnapshotSolverService(FakeCloudProvider())
        for method in ("Solve", "SolveClasses", "Health", "Consolidate", "LeaseGet", "LeaseApply"):

            class _Details:
                pass

            details = _Details()
            details.method = f"/{SERVICE}/{method}"
            assert service.service(details) is not None, method

    def test_solve_response_fields(self):
        from karpenter_core_tpu.service.snapshot_channel import (
            SnapshotSolverClient,
            serve,
        )

        server, port = serve(FakeCloudProvider())
        client = SnapshotSolverClient(f"127.0.0.1:{port}")
        try:
            response = client.solve(make_pods(2, requests={"cpu": 1}), [make_provisioner()])
            assert set(response) == {
                "newNodes", "existingAssignments", "failedPodIndices",
                "residualPodIndices", "existingCommittedZones",
            }
            node = response["newNodes"][0]
            assert set(node) == {
                "provisioner", "instanceTypes", "zones", "capacityTypes",
                "requests", "podIndices",
            }
        finally:
            client.close()
            server.stop(0)


class TestRemoteLeaseCAS:
    """Lease-plane compare-and-swap under CONCURRENT writers: only the happy
    path was pinned before — two elector replicas racing the same
    expectedVersion must yield exactly one winner and a version-conflict
    error for the loser (the property leader election's safety rests on)."""

    @pytest.fixture()
    def lease_server(self, tmp_path, monkeypatch):
        from karpenter_core_tpu.service.snapshot_channel import serve

        monkeypatch.setenv("KC_LEASE_STATE", str(tmp_path / "leases.json"))
        server, port = serve(FakeCloudProvider())
        yield f"127.0.0.1:{port}"
        server.stop(0)

    def test_messages_past_grpc_default_4mib_cross_both_ways(self, lease_server):
        """A SolveClasses answer at the north-star size is ~86 MB; gRPC's
        default cap is 4 MiB in each direction.  The lease plane echoes what
        it is sent, so one fat lease proves request AND response sizes
        without a solve."""
        from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverClient

        client = SnapshotSolverClient(lease_server)
        try:
            fat = "x" * (5 << 20)
            response = client.lease_apply(
                {"name": "fat", "namespace": "", "holderIdentity": fat},
                timeout=30.0,
            )
            assert response["ok"]
            assert response["lease"]["holderIdentity"] == fat
        finally:
            client.close()

    @staticmethod
    def _lease(name="leader", holder="", transitions=0):
        from karpenter_core_tpu.apis.objects import Lease, LeaseSpec, ObjectMeta

        return Lease(
            metadata=ObjectMeta(name=name, namespace="karpenter"),
            spec=LeaseSpec(
                holder_identity=holder,
                lease_duration_seconds=15,
                acquire_time=1.0,
                renew_time=1.0,
                lease_transitions=transitions,
            ),
        )

    def test_racing_updates_same_expected_version_one_winner(self, lease_server):
        import threading

        from karpenter_core_tpu.operator.kubeclient import ConflictError
        from karpenter_core_tpu.service.snapshot_channel import RemoteLeaseStore

        seed_store = RemoteLeaseStore(lease_server)
        created = seed_store.create(self._lease(holder="seed"))
        assert created.metadata.resource_version == 1

        stores = {w: RemoteLeaseStore(lease_server) for w in ("alpha", "beta")}
        outcomes = {}
        barrier = threading.Barrier(2)

        def race(who):
            barrier.wait()
            try:
                updated = stores[who].update_with_version(
                    self._lease(holder=who, transitions=1),
                    expected_resource_version=1,
                )
                outcomes[who] = ("won", updated.metadata.resource_version)
            except ConflictError as e:
                outcomes[who] = ("conflict", str(e))

        threads = [threading.Thread(target=race, args=(w,)) for w in stores]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        results = sorted(kind for kind, _ in outcomes.values())
        assert results == ["conflict", "won"], outcomes
        winner = next(w for w, (kind, _) in outcomes.items() if kind == "won")
        assert outcomes[winner][1] == 2
        # the stored lease is the winner's, exactly one version bump
        final = seed_store.get(None, "leader", "karpenter")
        assert final.spec.holder_identity == winner
        assert final.metadata.resource_version == 2

    def test_racing_creates_one_winner(self, lease_server):
        import threading

        from karpenter_core_tpu.operator.kubeclient import ConflictError
        from karpenter_core_tpu.service.snapshot_channel import RemoteLeaseStore

        stores = {w: RemoteLeaseStore(lease_server) for w in ("alpha", "beta")}
        outcomes = {}
        barrier = threading.Barrier(2)

        def race(who):
            barrier.wait()
            try:
                stores[who].create(self._lease(name="fresh", holder=who))
                outcomes[who] = "won"
            except ConflictError:
                outcomes[who] = "conflict"

        threads = [threading.Thread(target=race, args=(w,)) for w in stores]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(outcomes.values()) == ["conflict", "won"], outcomes
        winner = next(w for w, kind in outcomes.items() if kind == "won")
        final = stores["alpha"].get(None, "fresh", "karpenter")
        assert final.metadata.resource_version == 1
        assert final.spec.holder_identity == winner


class TestSettingsStore:
    def test_live_update(self):
        from karpenter_core_tpu.operator.kubeclient import KubeClient
        from karpenter_core_tpu.operator.settingsstore import (
            ConfigMap,
            SETTINGS_NAME,
            SettingsStore,
        )

        kube = KubeClient()
        store = SettingsStore(kube).start()
        assert store.batch_max_duration == 10.0
        cm = kube.get(ConfigMap, SETTINGS_NAME, "karpenter")
        cm.data = {"batchMaxDuration": "20s", "featureGates.driftEnabled": "true"}
        kube.update(cm)
        assert store.batch_max_duration == 20.0
        assert store.drift_enabled

    def test_invalid_update_keeps_last_good(self):
        from karpenter_core_tpu.operator.kubeclient import KubeClient
        from karpenter_core_tpu.operator.settingsstore import (
            ConfigMap,
            SETTINGS_NAME,
            SettingsStore,
        )

        kube = KubeClient()
        store = SettingsStore(kube).start()
        cm = kube.get(ConfigMap, SETTINGS_NAME, "karpenter")
        cm.data = {"batchMaxDuration": "not-a-duration"}
        kube.update(cm)
        assert store.batch_max_duration == 10.0


@pytest.mark.compile  # the device sweep compiles -- slow tier (`make test-all`)
class TestTPUConsolidationInController:
    def test_controller_uses_tpu_sweep(self):
        from tests.test_tpu_consolidation import build_cluster
        from karpenter_core_tpu.controllers.deprovisioning import Result

        env = build_cluster(n_nodes=2, pods_per_node=1, pod_cpu="500m", oversize=True)
        env.deprovisioning.multi_node_consolidation.use_tpu_kernel = True
        result, _ = env.deprovisioning.reconcile()
        assert result == Result.SUCCESS
        # consolidated: fewer nodes than before
        assert len(env.kube.list_nodes()) == 1


class TestLoggingConfig:
    def test_dynamic_log_level(self):
        import logging

        from karpenter_core_tpu.apis.objects import ObjectMeta
        from karpenter_core_tpu.operator.kubeclient import KubeClient
        from karpenter_core_tpu.operator.settingsstore import (
            ConfigMap,
            LoggingConfigWatcher,
        )

        kube = KubeClient()
        logger = logging.getLogger("kc-test-dynlog")
        logger.setLevel(logging.INFO)
        LoggingConfigWatcher(kube, logger_name="kc-test-dynlog").start()
        kube.create(
            ConfigMap(
                metadata=ObjectMeta(name="config-logging", namespace="karpenter"),
                data={"loglevel.controller": "debug"},
            )
        )
        assert logger.level == logging.DEBUG
        cm = kube.get(ConfigMap, "config-logging", "karpenter")
        cm.data["loglevel.controller"] = "bogus"
        kube.update(cm)
        assert logger.level == logging.DEBUG  # invalid keeps last good
        cm.data = {"unrelated": "x"}
        kube.update(cm)
        assert logger.level == logging.DEBUG  # absent key keeps current
