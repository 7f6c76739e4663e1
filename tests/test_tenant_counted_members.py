"""The tenant path carries a class's members as a count (models.store
``CountedUids`` / ``CountedPods``): the session diffs two counts, copies
only the members a delta places, and keeps a membership counted while it is
a prefix ``#0 … #n-1``.  The answer must be the one every member copied up
front gave: one lineage is driven through a scripted sequence of counts
twice — once as the service decodes it, once with every class's members
copied by the reference below — and every tick's delta, repair inputs,
reply bytes and lineage state must be equal."""

import copy

import numpy as np
import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import codec
from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
from karpenter_core_tpu.models import store as store_mod
from karpenter_core_tpu.policy import PolicyConfig
from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverService
from karpenter_core_tpu.solver import incremental as inc_mod
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pod, make_provisioner


def _materialize_class(rep, count: int, uid_base: str) -> list:
    """The service's copies of a class representative as they were made
    before members became counted: ``count`` copies, uid ``<base>#<j>``."""
    pods = []
    for j in range(count):
        pod = copy.copy(rep)
        pod.metadata = copy.copy(rep.metadata)
        pod.metadata.uid = f"{uid_base}#{j}"
        pods.append(pod)
    return pods


CPU = {"a": "500m", "b": "250m", "c": "750m", "d": "1"}
# (counts by class, the member of "a" the lineage placed that has since bound)
SCRIPT = [
    ({"a": 40, "b": 8, "c": 6}, None),          # anchor
    ({"a": 46, "b": 8, "c": 6}, None),          # grow
    ({"a": 46, "b": 1, "c": 6}, None),          # shrink to one
    ({"a": 46, "b": 1}, None),                  # c dropped
    ({"a": 46, "b": 1, "c": 4}, None),          # c back on its emptied row
    ({"a": 46, "b": 1, "c": 4, "d": 3}, None),  # d, never seen: a full solve
    ({"a": 50, "b": 3, "c": 4, "d": 3}, None),  # grow again
    ({"a": 49, "b": 3, "c": 4, "d": 3}, 3),     # a#3 bound out of the middle
    ({"a": 49, "b": 5, "c": 4, "d": 3}, None),  # a stays a uid tuple
]
MODES = ["full", "delta", "delta", "delta", "delta", "full", "delta", "delta",
         "delta"]


def _uids(mapping) -> list:
    return [(key, tuple(uids)) for key, uids in mapping.items()]


def _delta_view(d) -> tuple:
    return (d.from_version, d.to_version, _uids(d.added), _uids(d.evicted),
            d.new_classes, d.removed_classes, d.changed_planes,
            d.pods_before, d.pods_after)


def _plan_view(plan) -> dict:
    return {
        "counts": plan["counts"].tolist(),
        "free_new": plan["free_new"].tolist(),
        "free_ex": plan["free_ex"].tolist(),
        "evicted_locs": list(plan["evicted_locs"]),
        "pods_by_root": {row: [p.uid for p in pods]
                         for row, pods in plan["pods_by_root"].items()},
        "members_after": _uids(plan["members_after"]),
    }


def _drain() -> list:
    spans = [s for t in tracing.TRACE_STORE.last(None) for s in t.spans]
    tracing.TRACE_STORE.clear()
    return spans


def _run(monkeypatch, materialize: bool) -> list:
    """Every tick of SCRIPT through one lineage: what each tick produced.
    The service's handler does what this loop does: decode, a solver per
    request, the session's solve, the reply's payload and pack."""
    deltas, plans = [], []
    diff = inc_mod.diff_members

    def recording_diff(*args, **kw):
        deltas.append(diff(*args, **kw))
        return deltas[-1]

    plan_fn = inc_mod.IncrementalSolveSession._delta_plan

    def recording_plan(self, delta, by_uid):
        plan = plan_fn(self, delta, by_uid)
        plans.append(plan)
        return plan

    monkeypatch.setattr(inc_mod, "diff_members", recording_diff)
    monkeypatch.setattr(inc_mod.IncrementalSolveSession, "_delta_plan",
                        recording_plan)
    provider = FakeCloudProvider()
    reps = {name: make_pod(requests={"cpu": cpu}) for name, cpu in CPU.items()}
    prov = codec.provisioner_to_dict(make_provisioner())
    session = inc_mod.IncrementalSolveSession()
    ticks = []
    for counts, bound_j in SCRIPT:
        deltas.clear()
        plans.clear()
        req = {"podClasses": [{"pod": codec.pod_to_dict(reps[n]), "count": c}
                              for n, c in counts.items()],
               "provisioners": [prov]}
        (classes, uid_class, provisioners, daemonset_pods, state_nodes,
         bound, resolver) = SnapshotSolverService._decode_tenant_classes(req)
        if materialize:
            for cls in classes:
                cls.pods = _materialize_class(
                    cls.pods.rep, len(cls.pods), cls.pods.uids.base)
        if bound_j is not None:
            base_a = next(b for b, i in uid_class.items() if i == 0)
            member = copy.deepcopy(reps["a"])
            member.metadata.uid = f"{base_a}#{bound_j}"
            member.spec.node_name = "node-of-a"
            bound = list(bound) + [member]
        solver = TPUSolver(provider, provisioners, daemonset_pods,
                           kube_client=resolver,
                           policy=PolicyConfig.from_wire(None))
        session.rebind(solver)
        results = session.solve(classes, state_nodes or None, bound)

        def class_counts(pods):
            out = {}
            for p in pods:
                i = uid_class[p.uid.rsplit("#", 1)[0]]
                out[i] = out.get(i, 0) + 1
            return sorted(out.items())

        reply = SnapshotSolverService._pack_reply(
            SnapshotSolverService._classes_payload(results, class_counts))
        spans = _drain()
        diff_attrs = [s["attrs"] for s in spans if s["name"] == "session.diff"]
        plan_attrs = [s["attrs"] for s in spans if s["name"] == "session.plan"]
        ticks.append({
            "mode": session.last_mode,
            "delta": deltas[0] if deltas else None,
            "plan": plans[0] if plans else None,
            "reply": reply,
            "state": session.lineage_state(),
            "diff": diff_attrs[0],
            "plan_attrs": plan_attrs[0] if plan_attrs else None,
            "copied": (None if materialize
                       else sum(cls.pods.copied for cls in classes)),
        })
    ticks[-1]["export"] = session.export_lineage()
    return ticks


@pytest.fixture(scope="module")
def runs():
    """(counted, copied): SCRIPT as the service decodes it, and with every
    member copied up front — one lineage each, traced."""
    capacity = tracing.TRACE_STORE.capacity
    tracing.TRACE_STORE.clear()
    tracing.enable()
    try:
        out = []
        for materialize in (False, True):
            with pytest.MonkeyPatch.context() as mp:
                out.append(_run(mp, materialize))
        yield tuple(out)
    finally:
        tracing.disable()
        tracing.TRACE_STORE.clear()
        tracing.TRACE_STORE.set_capacity(capacity)


def test_counted_members_answer_what_copied_members_did(runs):
    counted, copied = runs
    assert [t["mode"] for t in counted] == [t["mode"] for t in copied] == MODES
    for tick, (got, want) in enumerate(zip(counted, copied)):
        assert (got["delta"] is None) == (want["delta"] is None), tick
        if got["delta"] is not None:
            assert got["delta"] == want["delta"], tick
            assert _delta_view(got["delta"]) == _delta_view(want["delta"]), tick
        if got["plan"] is not None:
            assert _plan_view(got["plan"]) == _plan_view(want["plan"]), tick
        assert got["reply"] == want["reply"], tick
        assert got["state"] == want["state"], tick
    # the checkpoint's membership rows read the same uids either way
    assert counted[-1]["export"]["members_rows"] == \
        copied[-1]["export"]["members_rows"]
    assert counted[-1]["export"]["pod_loc"] == copied[-1]["export"]["pod_loc"]


def test_counted_path_engages_where_both_sides_are_counts(runs):
    counted, copied = runs
    for tick, (got, want) in enumerate(zip(counted, copied)):
        if got["delta"] is None:
            continue
        n = len(SCRIPT[tick][0])
        # the materialized lineage diffs every key as a uid set
        assert (want["diff"]["counted_classes"], want["diff"]["uid_classes"]) \
            == (0, n), tick
        # a uid tuple from the bound member on: "a" is diffed by set
        by_set = 1 if tick >= 7 else 0
        assert (got["diff"]["counted_classes"], got["diff"]["uid_classes"]) \
            == (n - by_set, by_set), tick
        if MODES[tick] == "delta":
            placed = got["delta"].added_count
            assert got["plan_attrs"]["resolved"] == placed, tick
            assert want["plan_attrs"]["resolved"] == 0, tick
            # the members copied for the tick are the ones it places
            assert got["copied"] == placed, tick
    # "a" (the first class) went to a uid tuple when its member #3 bound,
    # and stayed one; every other class stays counted
    members = counted[-1]["plan"]["members_after"]
    a_key = next(iter(members))
    assert not isinstance(members[a_key], store_mod.CountedUids)
    assert all(isinstance(uids, store_mod.CountedUids)
               for key, uids in members.items() if key != a_key)


def test_departures_copy_nothing(runs):
    counted, _ = runs
    # tick 2 (b 8 -> 1) and tick 3 (c dropped) only take pods away
    for tick in (2, 3):
        assert counted[tick]["delta"].added_count == 0
        assert counted[tick]["plan_attrs"]["resolved"] == 0
        assert counted[tick]["copied"] == 0
        assert np.sum(counted[tick]["plan"]["counts"]) == 0
