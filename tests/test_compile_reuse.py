"""Steady-state compile reuse: varied reconcile batches, one executable.

VERDICT r2 #3 / ROADMAP gap 1: the compile cache keys on padded bucket shapes
(ops/solve.pad_planes), so nearby problem sizes — different class counts,
different pod counts, new label values, nodes joining — must reuse the same
compiled executable instead of paying a multi-second XLA compile inside the
10 s batch window (settings.go:39-40 parity).  compilecache.stats() meters
actual executable builds.
"""

import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pod, make_provisioner
from karpenter_core_tpu.utils import compilecache

pytestmark = pytest.mark.compile  # kernel compiles: the slow tier


def _mix(n_generic: int, n_spread: int, sizes):
    pods = [
        make_pod(requests=sizes[i % len(sizes)], labels={"app": f"gen-{i % len(sizes)}"})
        for i in range(n_generic)
    ]
    pods += [
        make_pod(
            labels={"app": "spread"},
            requests={"cpu": "250m"},
            topology_spread=[
                TopologySpreadConstraint(
                    max_skew=1,
                    topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                    label_selector=LabelSelector(match_labels={"app": "spread"}),
                )
            ],
        )
        for _ in range(n_spread)
    ]
    return pods


class TestSteadyStateCompileReuse:
    def test_varied_batches_reuse_one_executable(self):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(24))
        solver = TPUSolver(provider, [make_provisioner()])
        # a process's first reconcile: the memo and the slot / feature
        # hysteresis are the process's, and an earlier test file of the same
        # xdist worker that solved these shapes would leave nothing to build
        compilecache.reset_memo()

        # first batch pays the build
        r = solver.solve(_mix(40, 8, [{"cpu": "500m"}, {"cpu": 1}]))
        assert sum(len(n.pods) for n in r.new_nodes) == 48
        first = compilecache.stats()
        assert first["builds"] >= 1

        # steady state: class count wobbles (2-5 classes, same C bucket of 8),
        # pod counts wobble (same slot bucket), label VALUES churn (same
        # vocab bucket) — zero new executables
        varied = [
            _mix(37, 11, [{"cpu": "500m"}, {"cpu": 1}]),
            _mix(44, 4, [{"cpu": "500m"}, {"cpu": 1}, {"cpu": 2}]),
            _mix(40, 8, [{"cpu": "250m"}]),
            _mix(51, 0, [{"cpu": "500m"}, {"memory": "1Gi"}]),
        ]
        for pods in varied:
            results = solver.solve(pods)
            assert sum(len(n.pods) for n in results.new_nodes) == len(pods)
        after = compilecache.stats()
        assert after["builds"] == first["builds"], (
            f"steady-state batches recompiled: {after} vs {first}"
        )
        assert after["memo_hits"] >= len(varied)

    def test_node_churn_within_bucket_reuses_executable(self):
        """Nodes joining (existing-node plane E grows within its bucket) must
        not recompile; crossing the bucket boundary may."""
        from karpenter_core_tpu.testing.harness import make_environment

        env = make_environment()
        env.kube.create(make_provisioner())
        env.provisioning.use_tpu_kernel = True
        env.provisioning.tpu_kernel_min_pods = 4

        from karpenter_core_tpu.testing.harness import expect_provisioned

        pods = [make_pod(requests={"cpu": "100m"}) for _ in range(8)]
        result = expect_provisioned(env, *pods)
        assert all(result[p.uid] is not None for p in pods)
        env.make_all_nodes_ready()
        compilecache.reset_stats()

        # second reconcile now has existing nodes: E goes 0 -> k, new variant
        pods2 = [make_pod(requests={"cpu": "100m"}) for _ in range(8)]
        result = expect_provisioned(env, *pods2)
        assert all(result[p.uid] is not None for p in pods2)
        ex_build = compilecache.stats()["builds"]

        # third and fourth reconciles: node count changed within the E bucket
        # (bucket floor is 8, ops/solve.pad_planes) — the ex-variant
        # executable must be reused
        for _ in range(2):
            batch = [make_pod(requests={"cpu": "100m"}) for _ in range(8)]
            result = expect_provisioned(env, *batch)
            assert all(result[p.uid] is not None for p in batch)
            env.make_all_nodes_ready()
        assert compilecache.stats()["builds"] == ex_build, "node churn recompiled"

    def test_warmup_precompiles_the_real_batch_shape(self):
        """TPUSolver.warmup's synthetic mix must land in the same shape
        buckets as a real steady-state batch, so the batch-window speculative
        compile (provisioning controller) makes the first real solve free."""
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(24))
        solver = TPUSolver(provider, [make_provisioner()])
        assert solver.warmup(n_pods=96)
        compilecache.reset_stats()

        pods = _mix(80, 16, [{"cpu": "500m"}, {"cpu": 1}, {"cpu": "250m"}])
        results = solver.solve(pods)
        assert sum(len(n.pods) for n in results.new_nodes) == len(pods)
        assert compilecache.stats()["builds"] == 0, "real batch recompiled after warmup"

    def test_member_count_wobble_reuses_executable(self):
        """The member list's width M is a SHAPE (``ops.solve.member_index``:
        the bucket of the largest member count, floor 8), so a batch whose
        classes sit in one group each and a batch whose classes sit in three
        run the same executable."""
        import numpy as np

        zone, host = labels_api.LABEL_TOPOLOGY_ZONE, labels_api.LABEL_HOSTNAME

        def batch(shared: bool):
            pods = [make_pod(requests={"cpu": "500m"}, labels={"app": "gen"}) for _ in range(20)]
            for app, key, skew in (("s1", zone, 1), ("s2", host, 4), ("s3", zone, 2)):
                labels = {"app": app, "tier": "t"}
                selector = {"tier": "t"} if shared else {"app": app}
                pods += [
                    make_pod(
                        labels=labels, requests={"cpu": "250m"},
                        topology_spread=[TopologySpreadConstraint(
                            max_skew=skew, topology_key=key,
                            label_selector=LabelSelector(match_labels=selector),
                        )],
                    )
                    for _ in range(6)
                ]
            return pods

        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(24))
        solver = TPUSolver(provider, [make_provisioner()])
        widths = []
        for shared in (False, True):
            snapshot = solver.encode(batch(shared))
            widths.append((
                int(np.asarray(snapshot.grp_member).sum(axis=1).max()),
                np.shape(solver.prepare_encoded(snapshot).cls.member_idx)[1],
            ))
        assert widths == [(1, 8), (3, 8)]
        compilecache.reset_memo()  # as above: the first solve must find nothing built
        solver.solve(batch(False))
        first = compilecache.stats()["builds"]
        assert first >= 1
        solver.solve(batch(True))
        assert compilecache.stats()["builds"] == first, "a member count of 3 recompiled"

    def test_suite_sizes_compile_what_they_compiled(self):
        """The upstream suite's seven batch sizes (benchmark/configs/
        upstream-suite-400.json, its own pod mix) build seven executables
        through the library path at 24 types — the count before the member
        list existed: one member per class, so M is 8 at every size and adds
        no shape of its own.  Counted in a process of its own: the memo of
        this one holds whatever earlier tests built."""
        import json
        import os
        import pathlib
        import subprocess
        import sys

        root = pathlib.Path(__file__).parent.parent
        script = (
            "import json, numpy as np\n"
            "from benchmark.harness.podmix import pod_mix, seeded\n"
            "from karpenter_core_tpu.cloudprovider import fake as fake_cp\n"
            "from karpenter_core_tpu.solver.tpu import TPUSolver\n"
            "from karpenter_core_tpu.testing import make_provisioner\n"
            "from karpenter_core_tpu.utils import compilecache\n"
            "config = json.load(open('benchmark/configs/upstream-suite-400.json'))\n"
            "solver = TPUSolver(fake_cp.FakeCloudProvider(fake_cp.instance_types(24)),"
            " [make_provisioner()])\n"
            "widths, placed = set(), []\n"
            "for j, n in enumerate(config['batch_sizes']):\n"
            "    pods = pod_mix(n, seeded(11, f'batch{j}'), config['pod_mix'])\n"
            "    prep = solver.prepare_encoded(solver.encode(pods))\n"
            "    widths.add(int(np.shape(prep.cls.member_idx)[1]))\n"
            "    placed.append(sum(len(x.pods) for x in solver.solve(pods).new_nodes))\n"
            "print(json.dumps({'builds': compilecache.stats()['builds'],"
            " 'widths': sorted(widths), 'placed': placed,"
            " 'sizes': config['batch_sizes']}))\n"
        )
        env = dict(
            os.environ, JAX_PLATFORMS="cpu", KC_TPU_WARMUP="0", KC_SOLVER_MESH="0",
            PYTHONPATH=str(root), KC_TPU_COMPILE_CACHE=str(root / ".kc_cache" / "suite_sizes"),
        )
        proc = subprocess.run(
            [sys.executable, "-c", script], cwd=root, env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen = json.loads(proc.stdout.strip().splitlines()[-1])
        assert seen["placed"] == seen["sizes"]
        assert seen["widths"] == [8]
        assert seen["builds"] == len(seen["sizes"]) == 7

    def test_bucket_grid_is_stable(self):
        from karpenter_core_tpu.ops.solve import bucket

        # the grid: powers of two and 1.5x powers of two, monotone, <=33% waste
        for n in range(1, 4000, 37):
            b = bucket(n)
            assert b >= n
            assert b <= max(2 * n, 8)
        vals = sorted({bucket(n) for n in range(1, 2000)})
        waste = [(b2 - b1) / b1 for b1, b2 in zip(vals, vals[1:])]
        assert max(waste) <= 0.5 + 1e-9
