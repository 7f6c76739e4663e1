"""Parity for the phase-fused, statically-pruned, bit-packed solve program
(docs/KERNEL_PERF.md).

  - the mask-op algebra, standalone (fast, no kernel compile): every
    ops/masks.py operation must agree packed vs bool on random requirement
    tensors, bounds included — the bool layout is what models/snapshot.py
    encodes, the packed one what the kernel runs on
  - the zone-committal block (``committal_block``: zone-spread quotas,
    required zonal anti-affinity) against the HOST ORACLE
    (solver/scheduler.py) through the real controller, at tier-1 size
  - ``features``: static phase pruning vs the all-phases trace — a pruned
    family must have been a provable no-op.  These compile 2 full solve
    programs per case, so they carry the ``slow`` marker (excluded from the
    budgeted tier-1 run, included in ``make test-all``)
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    LabelSelector,
    PodAffinityTerm,
    TopologySpreadConstraint,
)
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.ops import masks as mask_ops
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pod, make_provisioner
from karpenter_core_tpu.testing.harness import expect_provisioned, make_environment
from karpenter_core_tpu.testing.validator import expect_valid_placements

ZONE = labels_api.LABEL_TOPOLOGY_ZONE
HOSTNAME = labels_api.LABEL_HOSTNAME

SIZES = (
    {"cpu": "100m"},
    {"cpu": "500m"},
    {"cpu": 1, "memory": "1Gi"},
    {"cpu": "250m", "memory": "512Mi"},
)


# -- mask-op algebra: packed vs bool, standalone (fast) -----------------------


def _random_req(rng: random.Random, batch: int, k: int, v: int):
    """Random bool-layout ReqTensor batch + valid plane + vocab ints."""
    mask = np.zeros((batch, k, v + 1), dtype=bool)
    defined = np.zeros((batch, k), dtype=bool)
    negative = np.zeros((batch, k), dtype=bool)
    gt = np.full((batch, k), -np.inf, dtype=np.float32)
    lt = np.full((batch, k), np.inf, dtype=np.float32)
    for b in range(batch):
        for key in range(k):
            shape = rng.random()
            if shape < 0.3:  # undefined: all-ones mask
                mask[b, key, :] = True
            elif shape < 0.6:  # In set
                defined[b, key] = True
                for s in range(v):
                    mask[b, key, s] = rng.random() < 0.4
            else:  # complement (NotIn / Exists)
                defined[b, key] = True
                negative[b, key] = rng.random() < 0.5
                mask[b, key, :] = True
                for s in range(v):
                    if rng.random() < 0.3:
                        mask[b, key, s] = False
            if rng.random() < 0.2:
                gt[b, key] = rng.randint(-3, 3)
            if rng.random() < 0.2:
                lt[b, key] = rng.randint(4, 12)
    valid = np.zeros((k, v + 1), dtype=bool)
    valid[:, :v] = True
    vocab_ints = np.where(
        np.random.default_rng(rng.randint(0, 1 << 30)).random((k, v)) < 0.5,
        np.arange(v, dtype=np.float32)[None, :],
        np.inf,
    )
    t = mask_ops.ReqTensor(
        jnp.asarray(mask), jnp.asarray(defined), jnp.asarray(negative),
        jnp.asarray(gt), jnp.asarray(lt),
    )
    return t, jnp.asarray(valid), jnp.asarray(vocab_ints)


@pytest.mark.parametrize("seed", range(8))
def test_packed_mask_ops_match_bool_ops(seed):
    rng = random.Random(seed)
    k, v = rng.randint(1, 5), rng.randint(1, 40)
    a, valid, ints = _random_req(rng, rng.randint(1, 6), k, v)
    b, _, _ = _random_req(rng, 1, k, v)
    is_custom = jnp.asarray(
        np.random.default_rng(seed).random(k) < 0.5
    )
    khb = tuple(
        bool(np.isfinite(np.asarray(t.gt)).any() or np.isfinite(np.asarray(t.lt)).any())
        for t in (a,)
        for _ in range(1)
    ) * k  # per-key conservative: bounds possible on every key
    width = v + 1
    pa, pb = mask_ops.pack_req(a), mask_ops.pack_req(b)
    pvalid = mask_ops.pack_mask(valid)

    np.testing.assert_array_equal(
        np.asarray(mask_ops.nonempty_intersection(a, b, ints)),
        np.asarray(mask_ops.nonempty_intersection(pa, pb, ints, v=width)),
    )
    np.testing.assert_array_equal(
        np.asarray(mask_ops.intersects(a, b, ints)),
        np.asarray(mask_ops.intersects(pa, pb, ints, v=width)),
    )
    np.testing.assert_array_equal(
        np.asarray(mask_ops.compatible(a, b, is_custom, ints)),
        np.asarray(mask_ops.compatible(pa, pb, is_custom, ints, v=width)),
    )
    got = mask_ops.add(pa, pb, pvalid, ints, v=width, key_has_bounds=khb)
    want = mask_ops.add(a, b, valid, ints)
    np.testing.assert_array_equal(
        np.asarray(want.mask), mask_ops.unpack_mask(np.asarray(got.mask), width)
    )
    for field in ("defined", "negative", "gt", "lt"):
        np.testing.assert_array_equal(
            np.asarray(getattr(want, field)), np.asarray(getattr(got, field)),
            err_msg=field,
        )
    np.testing.assert_array_equal(
        np.asarray(mask_ops.count_allowed(a, valid)),
        np.asarray(mask_ops.count_allowed(pa, pvalid, v=width)),
    )
    np.testing.assert_array_equal(
        np.asarray(mask_ops.single_value(a)),
        np.asarray(mask_ops.single_value(pa, v=width)),
    )


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    for m in (1, 7, 31, 32, 33, 64, 90):
        bits = rng.random((3, 2, m)) < 0.5
        words = np.asarray(mask_ops.pack_mask(jnp.asarray(bits)))
        assert words.shape[-1] == mask_ops.words_for(m)
        np.testing.assert_array_equal(
            mask_ops.unpack_mask(words, m), bits
        )
        # pad bits beyond m stay zero: OR over all rows never sets them
        if m % 32:
            top = words[..., -1] >> (m % 32)
            assert not np.any(top)


# -- the committal block vs the host oracle ------------------------------------


def _zone_spread(rng: random.Random, app: str):
    return dict(
        labels={"app": app}, requests=rng.choice(SIZES),
        topology_spread=[TopologySpreadConstraint(
            max_skew=rng.choice((1, 2)), topology_key=ZONE,
            label_selector=LabelSelector(match_labels={"app": app}))],
    )


def _zone_anti(rng: random.Random, app: str):
    return dict(
        labels={"app": app}, requests=rng.choice(SIZES),
        pod_anti_affinity=[PodAffinityTerm(
            topology_key=ZONE,
            label_selector=LabelSelector(match_labels={"app": app}))],
    )


def _committal_batch(family: str, seed: int):
    """<= 300 pods: one or two classes of each committal family asked for,
    among generic, hostname-spread and hostname-anti classes (the families
    whose single-batch counts equal the host's exactly)."""
    rng = random.Random(seed)
    pods = []
    if family in ("zone-spread", "both"):
        for i in range(rng.randint(1, 2)):
            kwargs = _zone_spread(rng, f"zs{i}")
            pods.extend(make_pod(**kwargs) for _ in range(rng.randint(7, 60)))
    if family in ("zone-anti", "both"):
        for i in range(rng.randint(1, 2)):
            kwargs = _zone_anti(rng, f"za{i}")
            pods.extend(make_pod(**kwargs) for _ in range(rng.randint(2, 6)))
    for i in range(rng.randint(2, 4)):
        labels = {"app": f"c{i}"}
        kwargs = dict(labels=labels, requests=rng.choice(SIZES))
        shape = rng.random()
        if shape < 0.3:
            kwargs["topology_spread"] = [TopologySpreadConstraint(
                max_skew=rng.choice((1, 2)), topology_key=HOSTNAME,
                label_selector=LabelSelector(match_labels=dict(labels)))]
        elif shape < 0.5:
            kwargs["pod_anti_affinity"] = [PodAffinityTerm(
                topology_key=HOSTNAME,
                label_selector=LabelSelector(match_labels=dict(labels)))]
        pods.extend(make_pod(**kwargs) for _ in range(rng.randint(2, 40)))
    assert len(pods) <= 300
    return pods


def _provision(family: str, seed: int, use_kernel: bool, batches: int):
    """(scheduled per class, failed, nodes) after up to ``batches``
    reconciles of one batch through the real controller, every placement
    checked by the independent validity oracle."""
    env = make_environment(instance_types=fake_cp.instance_types(16))
    env.kube.create(make_provisioner(name="default"))
    env.provisioning.use_tpu_kernel = use_kernel
    env.provisioning.tpu_kernel_min_pods = 1
    pods = _committal_batch(family, seed)
    scheduled = {}
    for batch in range(batches):
        if batch:
            env.make_all_nodes_ready()
            env.clock.step(21)
        result = expect_provisioned(env, *pods)
        expect_valid_placements(env, pods)
        placed = [p for p in pods if result[p.uid] is not None]
        for pod in placed:
            app = pod.metadata.labels["app"]
            scheduled[app] = scheduled.get(app, 0) + 1
        if not placed:
            break
    failed = len(pods) - sum(scheduled.values())
    return scheduled, failed, len(env.kube.list_nodes())


@pytest.mark.compile
@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("family", ["zone-spread", "zone-anti", "both"])
def test_committal_block_matches_host_oracle(family, seed):
    """The kernel's ONE batch against the host oracle on scheduled (per
    class), failed and node count.  Zone spread: the host's one batch,
    all three equal.  Required zonal anti-affinity: the host admits one
    member a batch as each node registers its zone
    (topology_test.go:1879-1923) where the committal block places one member
    per admissible zone at once, so the oracle is the host reconciled until
    it stops moving — the same fixpoint, reached over batches, each of which
    may launch a node the kernel's single batch shared."""
    anti = family != "zone-spread"
    host = _provision(family, seed, use_kernel=False, batches=6 if anti else 1)
    kernel = _provision(family, seed, use_kernel=True, batches=1)
    assert kernel[0] == host[0], f"scheduled: kernel={kernel[0]} host={host[0]}"
    assert kernel[1] == host[1], f"failed: kernel={kernel[1]} host={host[1]}"
    if anti:
        assert kernel[2] <= host[2], f"nodes: kernel={kernel[2]} host={host[2]}"
    else:
        assert kernel[2] == host[2], f"nodes: kernel={kernel[2]} host={host[2]}"


# -- kernel parity: pruned vs the all-phases trace ------------------------------


def _random_batch(rng: random.Random, with_ports: bool = False):
    """A randomized kernel-supported pod batch exercising the committal phase
    families (zone spread, required zonal anti) plus host families."""
    pods = []
    n_classes = rng.randint(3, 6)
    for i in range(n_classes):
        labels = {"app": f"c{i}"}
        kwargs = dict(labels=labels, requests=rng.choice(SIZES))
        shape = rng.random()
        if shape < 0.30:
            kwargs["topology_spread"] = [
                TopologySpreadConstraint(
                    max_skew=rng.choice((1, 2)),
                    topology_key=rng.choice((ZONE, HOSTNAME)),
                    label_selector=LabelSelector(match_labels=dict(labels)),
                )
            ]
        elif shape < 0.50:
            kwargs["pod_anti_affinity"] = [
                PodAffinityTerm(
                    topology_key=rng.choice((ZONE, HOSTNAME)),
                    label_selector=LabelSelector(match_labels=dict(labels)),
                )
            ]
        elif shape < 0.65:
            kwargs["pod_affinity"] = [
                PodAffinityTerm(
                    topology_key=rng.choice((ZONE, HOSTNAME)),
                    label_selector=LabelSelector(match_labels=dict(labels)),
                )
            ]
        if with_ports and i == 0:
            kwargs["host_ports"] = [8080 + i]
        pods.extend(make_pod(**kwargs) for _ in range(rng.randint(2, 12)))
    return pods


def _solve_variant(cls, sa, n_slots, khb, n_passes, **kw):
    out = solve_ops._solve_jit(cls, sa, n_slots, khb, n_passes=n_passes, **kw)
    return jax.device_get((
        out.assign, out.assign_existing, out.failed, out.spread_suspect,
        out.state.used, out.state.zone, out.state.ct, out.state.viable,
        out.state.pod_count, out.state.tmpl_id, out.state.open_, out.state.n_next,
        out.ex_state.used, out.ex_state.zone, out.ex_state.pod_count,
    ))


_FIELDS = ("assign", "assign_existing", "failed", "spread_suspect", "used",
           "zone", "ct", "viable", "pod_count", "tmpl_id", "open_", "n_next",
           "ex_used", "ex_zone", "ex_pod_count")


def _assert_same(ref, got, label):
    for name, a, b in zip(_FIELDS, ref, got):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"{label}: {name}"
        )


@pytest.mark.compile
@pytest.mark.slow
@pytest.mark.parametrize("seed", range(4))
def test_pruned_kernel_matches_all_phases_trace(seed):
    """The snapshot's phase plan (``features``) must produce SolveOutputs
    identical to the unpruned trace on randomized snapshots."""
    rng = random.Random(1000 + seed)
    provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(16))
    solver = TPUSolver(provider, [make_provisioner()])
    pods = _random_batch(rng, with_ports=(seed == 1))
    snap = solver.encode(pods)
    cls, sa, khb = solve_ops.prepare_host(snap)
    n_slots = solve_ops.estimate_slots(snap)
    ft = solve_ops.snapshot_features(snap)

    ref = _solve_variant(cls, sa, n_slots, khb, snap.scan_passes, features=None)
    prod = _solve_variant(cls, sa, n_slots, khb, snap.scan_passes, features=ft)
    _assert_same(ref, prod, f"seed {seed} pruned-vs-all-phases")


@pytest.mark.compile
@pytest.mark.slow
def test_pruned_parity_with_existing_nodes():
    """The pruned volume/port families against the all-phases trace, with
    real state nodes in play."""
    from karpenter_core_tpu.testing.harness import make_environment
    from karpenter_core_tpu.testing import make_node

    env = make_environment(instance_types=fake_cp.instance_types(16))
    env.kube.create(make_provisioner(name="default"))
    it = env.provider.get_instance_types(None)[4]
    offering = next(o for o in it.offerings if o.available)
    for i in range(3):
        node = make_node(
            name=f"ex-{i}",
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: it.name,
                labels_api.LABEL_TOPOLOGY_ZONE: offering.zone,
                labels_api.LABEL_CAPACITY_TYPE: offering.capacity_type,
                labels_api.LABEL_NODE_INITIALIZED: "true",
            },
            allocatable=it.allocatable(),
            capacity=dict(it.capacity),
            provider_id=f"fake://ex-{i}",
        )
        env.kube.create(node)
    state_nodes = env.cluster.snapshot_nodes()
    solver = TPUSolver(env.provider, env.kube.list_provisioners())
    rng = random.Random(21)
    pods = _random_batch(rng)
    snap = solver.encode(pods, state_nodes=state_nodes)
    ex_state, ex_static = solver.encode_existing(snap, state_nodes)
    cls, sa, khb = solve_ops.prepare_host(snap)
    n_slots = solve_ops.estimate_slots(snap)
    ft = solve_ops.features_with_existing(snap, ex_static)

    def run(**kw):
        out = solve_ops._solve_jit(
            cls, sa, n_slots, khb, ex_state, ex_static,
            n_passes=snap.scan_passes, **kw,
        )
        return jax.device_get((
            out.assign, out.assign_existing, out.failed, out.spread_suspect,
            out.state.used, out.state.zone, out.state.pod_count, out.state.n_next,
            out.ex_state.used, out.ex_state.zone, out.ex_state.pod_count,
            out.ex_state.vol_used,
        ))

    ref = run(features=None)
    prod = run(features=ft)
    for i, (a, b) in enumerate(zip(ref, prod)):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b), err_msg=f"field {i}"
        )
