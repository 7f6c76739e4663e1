"""Delta-native ingest/encode parity (ISSUE 11, docs/KERNEL_PERF.md Layer 6).

Three contracts pinned here:

  - the fast signature key (Python twin AND the kc_sig C extension) is EXACT:
    equal keys imply equal full class signatures, the interned signature
    equals the direct derivation for every shape, and the bulk ingest lands
    in the same final state as one-at-a-time adds;
  - the delta-consuming encode is BIT-IDENTICAL: randomized churn sequences
    produce plane-for-plane identical EncodedSnapshots (and identical store
    digests) on the reusing path vs a from-scratch encode on a fresh solver,
    and the store's commit skips re-hashing plane groups the encode shared
    by reference;
  - the prepared-plane fast paths (warm-prep reuse, device-side finishing)
    produce the same padded tensors and the same solve results.
"""

from __future__ import annotations

import copy
import random

import numpy as np
import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    LabelSelector,
    PodAffinityTerm,
    Toleration,
    TopologySpreadConstraint,
    new_uid,
)
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models import store as store_mod
from karpenter_core_tpu.models.columnar import (
    ColumnarPodBatch,
    PodIngest,
    SignatureInterner,
    _fast_sig_key,
    _fast_sig_key_py,
    classify_columnar,
)
from karpenter_core_tpu.models.snapshot import _class_signature
from karpenter_core_tpu.models.vocab import encode_value_set, encode_value_sets
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pod, make_provisioner


def _corpus(n_per_shape: int = 4):
    """A mixed-shape pod population covering every fast-key branch: simple,
    labeled/selected, tolerations, zone/host spread, self-affinity, plus the
    punt shapes (limits, host ports, PVC claims, init-free multi-container
    is not constructible via make_pod — limits covers the punt leg)."""
    shapes = [
        dict(requests={"cpu": "250m", "memory": "256Mi"}),
        dict(requests={"cpu": 1, "memory": "2Gi"}, labels={"app": "web"}),
        dict(requests={"cpu": "500m"}, node_selector={"disktype": "ssd"}),
        dict(
            requests={"cpu": "100m"},
            tolerations=[Toleration(key="dedicated", operator="Equal",
                                    value="batch", effect="NoSchedule")],
        ),
        dict(
            requests={"cpu": "250m"}, labels={"app": "zs"},
            topology_spread=[TopologySpreadConstraint(
                max_skew=1, topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                label_selector=LabelSelector(match_labels={"app": "zs"}),
            )],
        ),
        dict(
            requests={"cpu": "250m"}, labels={"app": "hs"},
            topology_spread=[TopologySpreadConstraint(
                max_skew=1, topology_key=labels_api.LABEL_HOSTNAME,
                label_selector=LabelSelector(match_labels={"app": "hs"}),
            )],
        ),
        dict(
            requests={"cpu": "250m"}, labels={"aff": "g1"},
            pod_affinity=[PodAffinityTerm(
                topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                label_selector=LabelSelector(match_labels={"aff": "g1"}),
            )],
        ),
        # punt shapes: the fast key must refuse these, never mis-key them
        dict(requests={"cpu": "100m"}, limits={"cpu": "200m"}),
        dict(requests={"cpu": "100m"}, host_ports=[8080]),
        dict(requests={"cpu": "100m"}, pvcs=["claim-a"]),
    ]
    pods = []
    for shape in shapes:
        for _ in range(n_per_shape):
            pods.append(make_pod(**copy.deepcopy(shape)))
    return pods


def _solver(n_types: int = 12):
    provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(n_types))
    return TPUSolver(provider, [make_provisioner(name="default")])


class TestFastSigKey:
    def test_interned_signature_exact(self):
        interner = SignatureInterner()
        for pod in _corpus():
            assert interner.sig_of(pod) == _class_signature(pod)

    def test_equal_keys_imply_equal_signatures(self):
        by_key = {}
        for pod in _corpus():
            key = _fast_sig_key_py(pod)
            if key is None:
                continue
            by_key.setdefault(key, []).append(_class_signature(pod))
        for sigs in by_key.values():
            assert len(set(sigs)) == 1

    def test_punt_shapes_return_none(self):
        for shape in (
            dict(requests={"cpu": "100m"}, limits={"cpu": "200m"}),
            dict(requests={"cpu": "100m"}, host_ports=[8080]),
            dict(requests={"cpu": "100m"}, pvcs=["claim-a"]),
        ):
            assert _fast_sig_key_py(make_pod(**shape)) is None

    def test_distinct_shapes_distinct_keys(self):
        """Every pair of corpus shapes with different signatures must have
        different fast keys (the exactness direction that prevents
        mis-classing)."""
        seen = {}
        for pod in _corpus(n_per_shape=1):
            key = _fast_sig_key_py(pod)
            if key is None:
                continue
            sig = _class_signature(pod)
            assert seen.setdefault(key, sig) == sig

    def test_c_extension_matches_python_twin(self):
        from karpenter_core_tpu.models import nativesig

        mod = nativesig.load()
        if mod is None:
            pytest.skip("kc_sig extension unavailable (no toolchain/headers)")
        for pod in _corpus():
            c_key = mod.fast_sig_key(pod)
            py_key = _fast_sig_key_py(pod)
            if c_key is NotImplemented:
                continue  # covered by the dispatcher fallback
            assert c_key == py_key
        # the dispatcher (whatever backs it) always equals the Python twin
        for pod in _corpus():
            assert _fast_sig_key(pod) == _fast_sig_key_py(pod)

    def test_c_extension_general_affinity_falls_back(self):
        from karpenter_core_tpu.models import nativesig

        mod = nativesig.load()
        if mod is None:
            pytest.skip("kc_sig extension unavailable (no toolchain/headers)")
        pod = make_pod(
            requests={"cpu": "100m"}, labels={"a": "1"},
            pod_anti_affinity=[PodAffinityTerm(
                topology_key=labels_api.LABEL_HOSTNAME,
                label_selector=LabelSelector(match_labels={"a": "1"}),
            )],
        )
        assert mod.fast_sig_key(pod) is NotImplemented
        assert _fast_sig_key(pod) == _fast_sig_key_py(pod)


class TestBulkIngest:
    def test_bulk_matches_sequential(self):
        pods = _corpus()
        seq, bulk = PodIngest(), PodIngest()
        for p in pods:
            seq.add(p)
        bulk.add_all(pods)
        assert seq.class_members() == bulk.class_members()
        assert seq.version == bulk.version == len(pods)
        assert len(seq) == len(bulk) == len(pods)

    def test_remove_all_then_revive(self):
        pods = _corpus()
        ingest = PodIngest()
        ingest.add_all(pods)
        members = ingest.class_members()
        for uid in [p.metadata.uid for p in pods]:
            assert ingest.remove(uid)
        assert len(ingest) == 0 and not ingest.class_members()
        ingest.add_all(pods)
        assert ingest.class_members() == members

    def test_re_add_replaces_with_two_mutations(self):
        ingest = PodIngest()
        pod = make_pod(requests={"cpu": "100m"})
        ingest.add(pod)
        v = ingest.version
        ingest.add(pod)
        assert ingest.version == v + 2  # remove + add, as before
        assert len(ingest) == 1

    def test_from_pods_matches_signature_hashes(self):
        pods = _corpus()
        batch = ColumnarPodBatch.from_pods(pods)
        for p, pod in enumerate(pods):
            expected = np.uint64(hash(_class_signature(pod)) & (2**64 - 1))
            assert batch.signature[p, 0] == expected
        grouped = classify_columnar(batch)
        # one class per distinct signature, counts preserved
        assert grouped.counts.sum() == len(pods)
        assert grouped.n_classes == len({_class_signature(p) for p in pods})


class TestEncodeValueSets:
    def test_matches_scalar_fuzz(self):
        from karpenter_core_tpu.scheduling import Requirement

        rng = random.Random(7)
        universe = [f"v{i}" for i in range(20)] + [str(i) for i in range(10)]
        reqs = [None]
        for _ in range(40):
            values = rng.sample(universe, rng.randint(0, 5))
            op = rng.choice(["In", "NotIn", "Exists", "Gt", "Lt"])
            if op == "In":
                reqs.append(Requirement("k", "In", values))
            elif op == "NotIn":
                reqs.append(Requirement("k", "NotIn", values))
            elif op == "Exists":
                reqs.append(Requirement("k", "Exists", []))
            elif op == "Gt":
                reqs.append(Requirement("k", "Gt", [str(rng.randint(0, 9))]))
            else:
                reqs.append(Requirement("k", "Lt", [str(rng.randint(0, 9))]))
        batch = encode_value_sets(reqs, universe)
        for i, req in enumerate(reqs):
            np.testing.assert_array_equal(batch[i], encode_value_set(req, universe))


def _assert_snapshots_identical(a, b):
    for _group, fields in store_mod.PLANE_FIELDS.items():
        for f in fields:
            x, y = getattr(a, f, None), getattr(b, f, None)
            if x is None and y is None:
                continue
            assert x is not None and y is not None, f
            x, y = np.asarray(x), np.asarray(y)
            assert x.dtype == y.dtype and x.shape == y.shape, f
            assert np.array_equal(x, y), f
    assert a.it_names == b.it_names and a.zones == b.zones
    assert a.capacity_types == b.capacity_types and a.resources == b.resources
    assert a.ports == b.ports
    assert tuple(a.features) == tuple(b.features)
    assert a.scan_passes == b.scan_passes
    assert store_mod.snapshot_digests(a) == store_mod.snapshot_digests(b)


class TestEncodeDeltaParity:
    def test_churn_fuzz_bit_identical(self):
        """Randomized churn: the persistent solver's (reusing) encodes must
        be plane-for-plane identical to a fresh solver's from-scratch
        encodes, tick after tick, including class births and deaths."""
        rng = random.Random(1729)
        solver = _solver()
        ingest = PodIngest()
        base = [p for p in _corpus(n_per_shape=6) if _fast_sig_key_py(p) is not None]
        ingest.add_all(base)
        reused_ticks = 0
        for tick in range(8):
            # churn: evict a random slice, re-mint replacements of the same
            # shapes, and occasionally birth a brand-new shape (forces a
            # reuse MISS: the class axis moved)
            uids = [p.metadata.uid for p in ingest.pods()]
            for uid in rng.sample(uids, k=max(1, len(uids) // 6)):
                ingest.remove(uid)
            rep = ingest.pods()[0]
            for i in range(rng.randint(1, 4)):
                pod = copy.deepcopy(rep)
                pod.metadata.name = f"churn-{tick}-{i}"
                pod.metadata.uid = new_uid()
                ingest.add(pod)
            if tick == 4:
                ingest.add(make_pod(
                    requests={"cpu": "750m"}, labels={"fresh": "shape"},
                ))
            snap = solver.encode(ingest)
            fresh = _solver()
            snap_fresh = fresh.encode(ingest)
            assert not snap_fresh.encode_reused
            _assert_snapshots_identical(snap, snap_fresh)
            reused_ticks += int(snap.encode_reused)
        assert reused_ticks >= 3  # the delta path actually engaged
        # and at least the new-shape tick missed
        assert reused_ticks < 8

    def test_store_commit_skips_unchanged_groups(self, monkeypatch):
        """Satellite 4: on a counts-only churn tick the commit re-hashes
        only the plane groups whose arrays actually changed (classes via
        cls_count, the recomputed policy planes) — never the catalog,
        template, vocab, or group planes the encode shared by reference."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all([p for p in _corpus(n_per_shape=5)
                        if _fast_sig_key_py(p) is not None])
        store = store_mod.SnapshotStore()
        store.commit(solver.encode(ingest))

        hashed_groups = []
        real = store_mod._digest_arrays

        def counting(arrays):
            hashed_groups.append(True)
            return real(arrays)

        # churn one class's membership (counts move, shapes don't)
        uid = ingest.pods()[0].metadata.uid
        rep = copy.deepcopy(ingest.get(uid))
        ingest.remove(uid)
        rep.metadata.uid = new_uid()
        rep.metadata.name = "churned"
        ingest.add(rep)
        snap = solver.encode(ingest)
        assert snap.encode_reused
        monkeypatch.setattr(store_mod, "_digest_arrays", counting)
        versioned = store.commit(snap)
        # counts unchanged in VALUE here (one out, one in, same class) —
        # cls_count was re-shared, so even the classes group digest reused;
        # only the freshly-attached policy planes re-hash
        assert len(hashed_groups) <= 2
        # digests still equal a from-scratch digest pass
        monkeypatch.setattr(store_mod, "_digest_arrays", real)
        assert versioned.digests == store_mod.snapshot_digests(snap)

    def test_supply_change_misses_reuse(self):
        """A price move invalidates the catalog planes but NOT the class
        planes; a template change invalidates the class planes too."""
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(8))
        solver = TPUSolver(provider, [make_provisioner(name="default")])
        ingest = PodIngest()
        ingest.add_all([make_pod(requests={"cpu": "250m"}) for _ in range(6)])
        s1 = solver.encode(ingest)
        it = provider.get_instance_types(None)[0]
        provider.set_price(it.name, 0.001,
                           capacity_type=it.offerings[0].capacity_type,
                           zone=it.offerings[0].zone)
        solver2 = TPUSolver(provider, [make_provisioner(name="default")])
        solver2._class_plane_cache = getattr(solver, "_class_plane_cache", None)
        solver2._catalog_cache = getattr(solver, "_catalog_cache", None)
        s2 = solver2.encode(ingest)
        # catalog planes rebuilt (price moved), class planes still reusable
        assert s2.it_price is not s1.it_price
        fresh = TPUSolver(provider, [make_provisioner(name="default")])
        s3 = fresh.encode(ingest)
        _assert_snapshots_identical(s2, s3)


class TestPreparedFastPaths:
    def test_prep_reuse_and_solve_parity(self):
        import jax

        solver = _solver(n_types=6)
        ingest = PodIngest()
        ingest.add_all([make_pod(requests={"cpu": "250m", "memory": "256Mi"})
                        for _ in range(32)])
        s1 = solver.encode(ingest)
        p1 = solver.prepare_encoded(s1)
        o1 = solver.run_prepared(p1)
        # churn a member: same shapes, new counts
        uid = ingest.pods()[0].metadata.uid
        ingest.remove(uid)
        s2 = solver.encode(ingest)
        assert s2.encode_reused
        p2 = solver.prepare_encoded(s2)
        assert p2.statics_arrays is p1.statics_arrays  # reused verbatim
        assert p2.cls.mask is p1.cls.mask
        assert p2.cls.count is not p1.cls.count  # the compact delta
        o2 = solver.run_prepared(p2)
        fresh = _solver(n_types=6)
        s3 = fresh.encode(ingest)
        p3 = fresh.prepare_encoded(s3)
        o3 = fresh.run_prepared(p3)
        a2, a3 = jax.device_get((o2.assign, o3.assign))
        assert np.array_equal(np.asarray(a2), np.asarray(a3))
        n2, n3 = jax.device_get((o2.state.n_next, o3.state.n_next))
        assert int(n2) == int(n3)

    def test_prep_reuse_skipped_with_state_nodes(self):
        """Existing-node planes are never served from the prep cache."""
        from karpenter_core_tpu.testing import make_node
        from karpenter_core_tpu.state.cluster import StateNode

        solver = _solver(n_types=6)
        ingest = PodIngest()
        ingest.add_all([make_pod(requests={"cpu": "250m"}) for _ in range(8)])
        snap = solver.encode(ingest)
        solver.prepare_encoded(snap)  # primes the cache
        it = solver.cloud_provider.get_instance_types(None)[0]
        node = make_node(
            name="n1",
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: it.name,
            },
            allocatable=it.allocatable(), capacity=dict(it.capacity),
        )
        prep = solver.prepare_encoded(snap, state_nodes=[StateNode(node)])
        assert prep.ex_state is not None


class TestSoakIngestProbe:
    def test_probe_registered_advisory(self):
        from karpenter_core_tpu.soak.slo import PROBES, Observation

        assert PROBES["ingest_s"] is False  # wall-clock => advisory
        obs = Observation(ingest_s=0.25)
        assert obs.probe_values()["ingest_s"] == 0.25


@pytest.mark.slow
class TestScaleParity:
    def test_100k_encode_parity(self):
        """The acceptance-scale cross-check: 100k pods x 2k types, delta vs
        from-scratch encodes bit-identical after a churn tick."""
        import chip_smoke

        solver, pods = chip_smoke.build_inputs(100_000, 2_000, n_provisioners=5)
        ingest = PodIngest()
        ingest.add_all(pods)
        solver.encode(ingest)
        uids = [p.metadata.uid for p in ingest.pods()[:2000]]
        reps = [copy.deepcopy(ingest.get(u)) for u in uids[:50]]
        for uid in uids:
            ingest.remove(uid)
        for i, rep in enumerate(reps * 4):
            pod = copy.deepcopy(rep)
            pod.metadata.uid = new_uid()
            pod.metadata.name = f"churn-{i}"
            ingest.add(pod)
        snap = solver.encode(ingest)
        assert snap.encode_reused
        fresh_solver, _ = chip_smoke.build_inputs(100, 2_000, n_provisioners=5)
        snap_fresh = fresh_solver.encode(ingest)
        _assert_snapshots_identical(snap, snap_fresh)
