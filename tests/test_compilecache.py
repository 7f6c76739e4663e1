"""Persistent compile/export caches (utils.compilecache) — the cold-start
eliminator.  The disk entries must round-trip (a second, cache-backed load
produces identical solve outputs) and invalidate on kernel-source change."""

import os

import numpy as np
import pytest

from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pods, make_provisioner
from karpenter_core_tpu.utils import compilecache

# exercises the export/XLA caches by compiling -- the slow tier (`make test-all`)
pytestmark = pytest.mark.compile

@pytest.fixture()
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("KC_TPU_COMPILE_CACHE", str(tmp_path))
    # reset module state (memo + slot hysteresis) so the fixture dir is
    # picked up and no stale slot count outlives its executable
    compilecache.reset_memo()
    yield tmp_path
    compilecache.reset_memo()

def _inputs():
    provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(10))
    solver = TPUSolver(provider, [make_provisioner()])
    ingest = PodIngest()
    ingest.add_all(make_pods(12, requests={"cpu": "500m"}))
    snap = solver.encode(ingest)
    host_cls, host_statics, khb = solve_ops.prepare_host(snap)
    return snap, host_cls, host_statics, khb

class TestExportCache:
    def test_roundtrip_matches_plain_jit(self, cache_dir):
        snap, cls, statics, khb = _inputs()
        n_slots = solve_ops.estimate_slots(snap)

        fn = compilecache.solve_callable(cls, statics, n_slots, khb)
        assert fn is not None
        entries = [f for f in os.listdir(cache_dir) if f.endswith(".stablehlo")]
        assert len(entries) == 1

        import jax

        dev_cls, dev_statics = jax.device_put((cls, statics))
        out_cached = fn(dev_cls, dev_statics)
        out_plain = solve_ops._solve_jit(dev_cls, dev_statics, n_slots, khb)
        assert np.array_equal(np.asarray(out_cached.assign), np.asarray(out_plain.assign))
        assert np.array_equal(np.asarray(out_cached.failed), np.asarray(out_plain.failed))

    def test_disk_entry_reused_after_memo_clear(self, cache_dir):
        snap, cls, statics, khb = _inputs()
        n_slots = solve_ops.estimate_slots(snap)
        compilecache.solve_callable(cls, statics, n_slots, khb)
        before = {f: os.path.getmtime(os.path.join(cache_dir, f))
                  for f in os.listdir(cache_dir) if f.endswith(".stablehlo")}
        compilecache.reset_memo()  # simulate a process restart
        fn = compilecache.solve_callable(cls, statics, n_slots, khb)
        assert fn is not None
        after = {f: os.path.getmtime(os.path.join(cache_dir, f))
                 for f in os.listdir(cache_dir) if f.endswith(".stablehlo")}
        assert before == after  # loaded, not re-exported

    def test_memo_hit_returns_same_object(self, cache_dir):
        snap, cls, statics, khb = _inputs()
        n_slots = solve_ops.estimate_slots(snap)
        a = compilecache.solve_callable(cls, statics, n_slots, khb)
        b = compilecache.solve_callable(cls, statics, n_slots, khb)
        assert a is b

    def test_distinct_configs_get_distinct_entries(self, cache_dir):
        snap, cls, statics, khb = _inputs()
        n_slots = solve_ops.estimate_slots(snap)
        compilecache.solve_callable(cls, statics, n_slots, khb)
        compilecache.solve_callable(cls, statics, n_slots * 2, khb)
        entries = [f for f in os.listdir(cache_dir) if f.endswith(".stablehlo")]
        assert len(entries) == 2

    def test_corrupt_entry_recovers(self, cache_dir):
        snap, cls, statics, khb = _inputs()
        n_slots = solve_ops.estimate_slots(snap)
        compilecache.solve_callable(cls, statics, n_slots, khb)
        (entry,) = [f for f in os.listdir(cache_dir) if f.endswith(".stablehlo")]
        with open(os.path.join(cache_dir, entry), "wb") as f:
            f.write(b"garbage")
        compilecache._memo.clear()
        fn = compilecache.solve_callable(cls, statics, n_slots, khb)
        assert fn is not None  # re-exported over the corrupt entry

    def test_solver_path_uses_cache(self, cache_dir):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(10))
        solver = TPUSolver(provider, [make_provisioner()])
        pods = make_pods(8, requests={"cpu": "900m"})
        res = solver.solve(pods)
        assert sum(len(n.pods) for n in res.new_nodes) == 8
        entries = [f for f in os.listdir(cache_dir) if f.endswith(".stablehlo")]
        assert entries, "TPUSolver.solve must populate the export cache"

class TestFeatureVariants:
    """SnapshotFeatures static pruning must map onto a BOUNDED set of trace
    variants (cache keys): snap_features widens a requested flag set to an
    already-built superset, and past MAX_FEATURE_VARIANTS distinct sets
    everything widens to all-on — so feature-keyed compilation can never
    silently explode (ISSUE 3 satellite)."""

    def setup_method(self):
        compilecache.reset_memo()

    def teardown_method(self):
        compilecache.reset_memo()

    def test_variant_space_is_bounded(self):
        import random

        from karpenter_core_tpu.ops.solve import ALL_FEATURES, SnapshotFeatures

        rng = random.Random(0)
        snapped_sets = set()
        for _ in range(500):
            bits = [rng.random() < 0.5 for _ in range(len(ALL_FEATURES))]
            f = SnapshotFeatures(*bits)
            snapped = compilecache.snap_features(f)
            # widening only: every flag the request needs stays on
            assert snapped.covers(f.canonical()), (f, snapped)
            snapped_sets.add(snapped)
        assert len(snapped_sets) <= compilecache.MAX_FEATURE_VARIANTS + 1

    def test_subset_request_reuses_superset_variant(self):
        from karpenter_core_tpu.ops.solve import ALL_FEATURES, SnapshotFeatures

        superset = ALL_FEATURES
        assert compilecache.snap_features(superset) == superset
        subset = SnapshotFeatures(*(False,) * len(superset))._replace(
            zone_spread=True
        )
        # the subset request lands on the already-seen superset — one
        # executable serves both (the extra phases are runtime no-ops)
        assert compilecache.snap_features(subset) == superset

    def test_canonicalization_collapses_implied_flags(self):
        from karpenter_core_tpu.ops.solve import SnapshotFeatures

        f = SnapshotFeatures(*(False,) * 11)._replace(required_zone_anti=True)
        c = f.canonical()
        assert c.zone_anti and c.inv_zone_anti
        # equivalent requests share one cache key
        g = f._replace(zone_anti=True, inv_zone_anti=True)
        assert compilecache.snap_features(f) == compilecache.snap_features(g)

    def test_none_means_all_on(self):
        from karpenter_core_tpu.ops.solve import ALL_FEATURES

        assert compilecache.snap_features(None) == ALL_FEATURES

    def test_encoded_snapshot_features_match_workload(self):
        from karpenter_core_tpu.apis import labels as labels_api
        from karpenter_core_tpu.apis.objects import (
            LabelSelector,
            TopologySpreadConstraint,
        )
        from karpenter_core_tpu.testing import make_pod

        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(10))
        solver = TPUSolver(provider, [make_provisioner()])
        pods = make_pods(4, requests={"cpu": "500m"}) + [
            make_pod(
                requests={"cpu": "250m"},
                labels={"app": "s"},
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                        label_selector=LabelSelector(match_labels={"app": "s"}),
                    )
                ],
            )
        ]
        snap = solver.encode(pods)
        ft = snap.features
        assert ft.zone_spread
        assert not ft.host_spread
        assert not ft.zone_affinity and not ft.host_affinity
        assert not ft.zone_anti and not ft.required_zone_anti
        assert not ft.host_ports and not ft.volume_limits


class TestShapeBuckets:
    """ops/solve.pad_planes: nearby problem sizes share one executable and
    padding is semantically invisible (ROADMAP compile-reuse item)."""

    @staticmethod
    def _solve_sig(results):
        return (
            sorted((d.provisioner_name, len(d.pods)) for d in results.new_nodes),
            sorted((name, len(pods)) for name, pods in results.existing_assignments.items()),
            len(results.failed_pods),
        )

    def test_bucket_grid(self):
        assert solve_ops.bucket(1) == 8
        assert solve_ops.bucket(8) == 8
        assert solve_ops.bucket(9) == 12
        assert solve_ops.bucket(13) == 16
        assert solve_ops.bucket(17) == 24
        assert solve_ops.bucket(25) == 32
        assert solve_ops.bucket(100) == 128
        assert solve_ops.bucket(3, floor=2) == 3
        assert solve_ops.bucket(5, floor=4) == 6

    def test_bucket_rungs_are_pow2_and_one_and_a_half_pow2(self):
        """The grid is 2, 3, 4, 6, 8, 12, ...: every rung a power of two or
        1.5x one, none skipped, whatever the floor."""
        rungs = sorted({solve_ops.bucket(n, floor=2) for n in range(1, 4097)})
        want, b = [], 2
        while b <= 4096:
            want += [b, b * 3 // 2]
            b *= 2
        assert rungs == [r for r in want if r <= 4096]
        for floor in (2, 4, 8):
            assert sorted(
                {solve_ops.bucket(n, floor=floor) for n in range(1, 4097)}
            ) == [r for r in want if floor <= r <= 4096]

    def test_bucket_is_monotone_and_covers_its_argument(self):
        prev = 0
        for n in range(1, 4097):
            b = solve_ops.bucket(n, floor=2)
            assert b >= n and b >= prev, n
            assert solve_ops.bucket(b, floor=2) == b, n  # a rung maps to itself
            assert n < 2 or 2 * b < 3 * n, n  # padding stays under one half
            prev = b

    def test_padding_parity(self, cache_dir, monkeypatch):
        from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint
        from karpenter_core_tpu.testing import make_pod

        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(10))
        solver = TPUSolver(provider, [make_provisioner()])
        pods = (
            make_pods(9, requests={"cpu": "1"})
            + make_pods(4, requests={"cpu": "2", "memory": "1Gi"})
            + [
                make_pod(
                    requests={"cpu": "500m"},
                    labels={"app": "spread"},
                    topology_spread=[
                        TopologySpreadConstraint(
                            max_skew=1,
                            topology_key="topology.kubernetes.io/zone",
                            label_selector=LabelSelector(match_labels={"app": "spread"}),
                        )
                    ],
                )
                for _ in range(6)
            ]
        )
        sigs = {}
        for buckets in ("0", "1"):
            monkeypatch.setenv("KC_TPU_SHAPE_BUCKETS", buckets)
            sigs[buckets] = self._solve_sig(solver.solve(pods))
        assert sigs["0"] == sigs["1"]

    def test_nearby_sizes_share_executable(self, cache_dir, monkeypatch):
        monkeypatch.setenv("KC_TPU_SHAPE_BUCKETS", "1")
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(10))
        solver = TPUSolver(provider, [make_provisioner()])
        solver.solve(
            make_pods(11, requests={"cpu": "1"}) + make_pods(5, requests={"cpu": "2"})
        )
        first = len(compilecache._memo)
        # different pod counts, one more class — same C/K/V buckets
        solver.solve(
            make_pods(14, requests={"cpu": "1"})
            + make_pods(3, requests={"cpu": "2"})
            + make_pods(2, requests={"memory": "512Mi"})
        )
        assert len(compilecache._memo) == first

    def test_padded_groups_and_existing_nodes(self, cache_dir, monkeypatch):
        """Topology groups + existing nodes keep exact results under padding."""
        from karpenter_core_tpu.apis import labels as labels_api
        from karpenter_core_tpu.apis.objects import LabelSelector, PodAffinityTerm
        from karpenter_core_tpu.testing import make_node, make_pod
        from karpenter_core_tpu.testing.harness import make_environment

        env = make_environment()
        node = make_node(
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                labels_api.LABEL_CAPACITY_TYPE: "spot",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                labels_api.LABEL_TOPOLOGY_ZONE: "test-zone-1",
            },
            allocatable={"cpu": 16, "memory": "64Gi", "pods": 110},
        )
        env.kube.create(node)
        solver = TPUSolver(env.provider, [make_provisioner()])
        pods = make_pods(6, requests={"cpu": "1"}) + [
            make_pod(
                requests={"cpu": "500m"},
                labels={"app": "anti"},
                pod_anti_affinity=[
                    PodAffinityTerm(
                        topology_key="kubernetes.io/hostname",
                        label_selector=LabelSelector(match_labels={"app": "anti"}),
                    )
                ],
            )
            for _ in range(3)
        ]
        sigs = {}
        for buckets in ("0", "1"):
            monkeypatch.setenv("KC_TPU_SHAPE_BUCKETS", buckets)
            sigs[buckets] = self._solve_sig(
                solver.solve(pods, state_nodes=env.cluster.snapshot_nodes())
            )
        assert sigs["0"] == sigs["1"]
        assert sigs["1"][1], "some pods should land on the existing node"
