"""The pod mix every cell sends: one general generator over the ``pod_mix``
block of the cell's configuration file, seeded.  Kept with the benchmark so
that no later PR can change the traffic it is measured on.

The block follows the upstream suite's makeDiversePods (aws/karpenter-core
pkg/controllers/provisioning/scheduling/scheduling_benchmark_test.go:185-197):
one seventh of the batch for each of five kinds — generic, zonal spread,
hostname spread, hostname pod affinity, zonal pod affinity — and what the
division leaves over as generic pods again; every pod of every kind draws cpu
and memory from the suite's lists (randomCPU / randomMemory, 30 shapes; 100m is
not exact in bf16, which is what lets a chip run catch a matmul below f32
precision) and its label from 7 values (randomLabelValue).

    {"parts_of": 7, "cpu": [...], "memory": [...], "label_values": [...],
     "kinds": [{"kind": "generic" | "spread" | "affinity", "parts": 1,
                "label_key": ..., "topology": "zone" | "hostname",
                "selector": "own" | "drawn"}, ...]}

``selector`` says what a constrained pod selects: ``own`` its own label,
``drawn`` a second, independent draw on the same key — the upstream suite as
written.  Where the configurations in use say ``own``, or put a kind on another
topology or key than upstream does, that is a departure forced by what the
kernel can serve today; each is named in the configuration's ``assumed`` and in
PERF.md section 7.  The first ``generic`` kind takes the remainder.
"""

import random

TOPOLOGY = {"zone": "LABEL_TOPOLOGY_ZONE", "hostname": "LABEL_HOSTNAME"}


def draw(n_pods: int, rng: random.Random, mix: dict) -> list:
    """``(workload, pod)`` pairs: ``workload`` is what was drawn for the pod —
    its kind's parameters, label, selected label, cpu, memory — so pods with
    equal workloads are replicas of one another, by the benchmark's own
    account and whatever classes the program makes of them."""
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.apis.objects import (
        LabelSelector,
        PodAffinityTerm,
        TopologySpreadConstraint,
    )
    from karpenter_core_tpu.testing import make_pod

    def one(kind: dict):
        key = kind["label_key"]
        value = rng.choice(mix["label_values"])
        cpu, memory = rng.choice(mix["cpu"]), rng.choice(mix["memory"])
        labels, requests = {key: value}, {"cpu": cpu, "memory": memory}
        if kind["kind"] == "generic":
            return (("generic", key), value, None, cpu, memory), make_pod(
                labels=labels, requests=requests)
        selected = value if kind["selector"] == "own" else rng.choice(mix["label_values"])
        selector = LabelSelector(match_labels={key: selected})
        topology_key = getattr(labels_api, TOPOLOGY[kind["topology"]])
        if kind["kind"] == "spread":
            pod = make_pod(labels=labels, requests=requests, topology_spread=[
                TopologySpreadConstraint(max_skew=1, topology_key=topology_key,
                                         label_selector=selector)])
        elif kind["kind"] == "affinity":
            pod = make_pod(labels=labels, requests=requests, pod_affinity=[
                PodAffinityTerm(topology_key=topology_key, label_selector=selector)])
        else:
            raise KeyError(f"pod_mix: no kind {kind['kind']!r}")
        return ((kind["kind"], kind["topology"], key), value, selected, cpu, memory), pod

    drawn = []
    for kind in mix["kinds"]:
        drawn += [one(kind) for _ in range(n_pods * kind["parts"] // mix["parts_of"])]
    filler = next(k for k in mix["kinds"] if k["kind"] == "generic")
    drawn += [one(filler) for _ in range(n_pods - len(drawn))]
    return drawn


def pod_mix(n_pods: int, rng: random.Random, mix: dict) -> list:
    return [pod for _workload, pod in draw(n_pods, rng, mix)]


def seeded(seed: int, stream: str) -> random.Random:
    """One independent, reproducible stream per (run seed, purpose): a string
    seed hashes the same way in every process."""
    return random.Random(f"{seed}:{stream}")
