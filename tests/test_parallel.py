"""Mesh-sharding tests on the virtual 8-device CPU platform."""

import pytest

import jax
import numpy as np

from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.parallel import mesh as mesh_ops
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pods, make_provisioner

# the virtual-mesh sharding suite traces + compiles study grids -- the slow tier (`make test-all`)
pytestmark = pytest.mark.compile


@pytest.fixture(autouse=True, scope="module")
def _fresh_compiler_state():
    """XLA:CPU's compiler can segfault when the 2D-mesh study grids compile
    in a process already holding hundreds of executables (observed 3x at the
    same suite position, in compile/serialize/deserialize paths; isolated
    runs always pass).  Dropping jax's in-process caches before this module
    gives the compiler a clean slate; the same crash class is why
    dryrun_multichip coverage rides the subprocess path below."""
    jax.clear_caches()
    from karpenter_core_tpu.utils import compilecache

    compilecache.reset_memo()
    yield
    jax.clear_caches()


def build(n_pods=24, n_types=6):
    provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(n_types))
    solver = TPUSolver(provider, [make_provisioner()])
    pods = make_pods(n_pods, requests={"cpu": "500m"})
    return solver, pods

class TestMonteCarloMesh:
    def test_replicas_shard_across_devices(self):
        solver, pods = build()
        snapshot = solver.encode(pods)
        mesh = mesh_ops.default_mesh(8)
        stats = mesh_ops.monte_carlo_solve(
            snapshot, n_replicas=16, mesh=mesh, interruption_rate=0.0
        )
        # rate 0: every replica identical, all pods scheduled
        assert (stats["scheduled"] == len(pods)).all()
        assert (stats["failed"] == 0).all()
        assert stats["cost_min"] == stats["cost_max"]

    def test_interruption_increases_cost_variance(self):
        solver, pods = build()
        snapshot = solver.encode(pods)
        mesh = mesh_ops.default_mesh(8)
        calm = mesh_ops.monte_carlo_solve(
            snapshot, n_replicas=16, mesh=mesh, interruption_rate=0.0
        )
        stormy = mesh_ops.monte_carlo_solve(
            snapshot, n_replicas=16, mesh=mesh, interruption_rate=0.9, seed=7
        )
        # spot knocked out: cost must not drop, and conservation holds
        assert stormy["cost_mean"] >= calm["cost_mean"] - 1e-6
        assert (stormy["scheduled"] + stormy["failed"] == len(pods)).all()

    def test_graft_entry_contract(self):
        import __graft_entry__ as graft

        fn, args = graft.entry()
        out = jax.jit(fn)(*args)
        assert int(np.asarray(out.assign).sum()) > 0

    def test_dryrun_multichip(self):
        # run the FULL dry run (monte-carlo, catalog-sharded solve,
        # consolidation lanes, crossed 2D grid) in a fresh interpreter — the
        # same way the driver invokes it, and immune to the accumulated
        # compiler state this suite builds up (_fresh_compiler_state)
        import __graft_entry__ as graft

        graft._dryrun_multichip_subprocess(8)

    def test_dryrun_multichip_subprocess(self):
        # A process that resolved some other platform (or too few devices)
        # runs the dry run in a child pinned to a virtual CPU mesh.
        import __graft_entry__ as graft

        graft._dryrun_multichip_subprocess(2)

class TestCrossedStudy:
    """2D (replica x lane) mesh: Monte-Carlo scenarios x consolidation
    prefixes in one sharded grid (parallel/mesh.py crossed_consolidation_study)."""

    def _existing(self, solver, snapshot, n_nodes=3):
        from karpenter_core_tpu.ops import solve as solve_ops

        n_classes = len(snapshot.classes)
        ex_state = solve_ops.empty_existing_state(
            len(snapshot.resources), snapshot.vocab.n_keys, snapshot.vocab.width,
            len(snapshot.zones), len(snapshot.capacity_types),
        )
        ex_static = solve_ops.empty_existing_static(
            len(snapshot.resources), n_classes, len(snapshot.groups) + 1
        )
        return ex_state, ex_static

    def test_grid_shape_and_sharding(self):
        solver, pods = build()
        snapshot = solver.encode(pods)
        mesh = mesh_ops.default_mesh_2d((4, 2))
        assert mesh.shape == {"replica": 4, "lane": 2}
        ex_state, ex_static = self._existing(solver, snapshot)
        n_classes = len(snapshot.classes)
        out = mesh_ops.crossed_consolidation_study(
            snapshot, ex_state, ex_static,
            candidate_rank=np.full(1, 1 << 30, dtype=np.int32),
            ex_cls_count=np.zeros((n_classes, 1), dtype=np.int32),
            prefix_sizes=np.arange(1, 6, dtype=np.int32),  # 5 lanes, pads to 6
            n_replicas=7,  # pads to 8
            mesh=mesh,
            interruption_rate=0.0,
        )
        assert out["failed"].shape == (7, 5)
        assert out["n_new"].shape == (7, 5)
        assert out["safe_prefix"].shape == (7,)

    def test_rate_zero_row_matches_1d_sweep(self):
        from karpenter_core_tpu.ops import consolidate as consolidate_ops

        solver, pods = build()
        snapshot = solver.encode(pods)
        ex_state, ex_static = self._existing(solver, snapshot)
        n_classes = len(snapshot.classes)
        rank = np.full(1, 1 << 30, dtype=np.int32)
        counts = np.zeros((n_classes, 1), dtype=np.int32)
        sizes = np.arange(1, 5, dtype=np.int32)

        sweep = consolidate_ops.run_sweep(
            snapshot, ex_state, ex_static, rank, counts, sizes
        )
        out = mesh_ops.crossed_consolidation_study(
            snapshot, ex_state, ex_static, rank, counts, sizes,
            n_replicas=4, mesh=mesh_ops.default_mesh_2d((2, 2)),
            interruption_rate=0.0,
        )
        # interruption rate 0: every replica row equals the plain 1D sweep
        for r in range(4):
            assert (out["failed"][r] == np.asarray(sweep.failed)).all()

    def test_interruptions_shrink_safe_prefix(self):
        # with heavy interruption some scenarios fail to re-schedule, so the
        # risk-aware safe prefix can only be <= the calm one
        solver, pods = build(n_pods=30, n_types=4)
        snapshot = solver.encode(pods)
        ex_state, ex_static = self._existing(solver, snapshot)
        n_classes = len(snapshot.classes)
        rank = np.full(1, 1 << 30, dtype=np.int32)
        counts = np.zeros((n_classes, 1), dtype=np.int32)
        sizes = np.arange(1, 5, dtype=np.int32)
        calm = mesh_ops.crossed_consolidation_study(
            snapshot, ex_state, ex_static, rank, counts, sizes,
            n_replicas=8, mesh=mesh_ops.default_mesh_2d((4, 2)),
            interruption_rate=0.0, seed=3,
        )
        stormy = mesh_ops.crossed_consolidation_study(
            snapshot, ex_state, ex_static, rank, counts, sizes,
            n_replicas=8, mesh=mesh_ops.default_mesh_2d((4, 2)),
            interruption_rate=0.95, seed=3,
        )
        assert stormy["safe_prefix_all"] <= calm["safe_prefix_all"]
        assert (stormy["failed"] >= calm["failed"]).all()

class TestTwoSliceDCN:
    """Virtual 2-slice layout (SURVEY §7.8 / VERDICT r2 missing #5): an
    8-device mesh built as (2 slices × 4 devices) with the replica axis on
    the OUTER dim — the dim that maps to DCN on multi-slice hardware.  The
    crossed study must partition with NO cross-device collectives: both batch
    axes are embarrassingly parallel, outputs stay sharded, and the only
    data movement is the host-side result fetch.  Proven by inspecting the
    compiled HLO for collective ops."""

    def _study_args(self, mesh, n_replicas=4, n_prefixes=4):
        import jax.numpy as jnp

        from karpenter_core_tpu.ops import solve as solve_ops

        solver, pods = build()
        snapshot = solver.encode(pods)
        n_classes = len(snapshot.classes)
        ex_state = solve_ops.empty_existing_state(
            len(snapshot.resources), snapshot.vocab.n_keys, snapshot.vocab.width,
            len(snapshot.zones), len(snapshot.capacity_types),
        )
        ex_static = solve_ops.empty_existing_static(
            len(snapshot.resources), n_classes, len(snapshot.groups) + 1
        )
        # mirror crossed_consolidation_study's own argument construction
        cls, statics_arrays, key_has_bounds = solve_ops.prepare(snapshot)
        avail_r = mesh_ops.perturb_spot_availability(
            snapshot, n_replicas, seed=0, interruption_rate=0.0
        )
        avail_idx = solve_ops.Statics._fields.index("it_avail")
        sizes = jnp.arange(1, n_prefixes + 1, dtype=jnp.int32)
        rank = jnp.full(1, 1 << 30, dtype=jnp.int32)
        counts = jnp.zeros((n_classes, 1), dtype=jnp.int32)
        fn = mesh_ops._crossed_grid_fn(
            mesh, key_has_bounds, 16, snapshot.scan_passes, avail_idx
        )
        return fn, (avail_r, sizes, cls, statics_arrays, ex_state, ex_static,
                    rank, counts), len(pods)

    def test_compiled_hlo_has_no_collectives(self):
        import re

        mesh = mesh_ops.default_mesh_2d((2, 4))
        assert mesh.devices.shape == (2, 4)
        assert mesh.axis_names == ("replica", "lane")  # replica outer = DCN
        fn, args, _ = self._study_args(mesh)
        with mesh:
            hlo = fn.lower(*args).compile().as_text()
        collectives = re.findall(
            r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
            r"reduce-scatter|collective-broadcast)\b",
            hlo,
        )
        assert not collectives, f"cross-device collectives in the study: {set(collectives)}"

    def test_outputs_stay_sliced_per_device(self):
        import numpy as np

        mesh = mesh_ops.default_mesh_2d((2, 4))
        fn, args, n_pods = self._study_args(mesh, n_replicas=4, n_prefixes=4)
        with mesh:
            failed, n_new = fn(*args)
        # each device holds exactly its (replica-block, lane-block) tile:
        # nothing was gathered cross-slice
        assert failed.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("replica", "lane")
            ),
            ndim=2,
        )
        for shard in failed.addressable_shards:
            assert shard.data.shape == (2, 1)  # [4/2 replicas, 4/4 lanes]
        # rate 0 + no real candidates: nothing fails in any cell
        assert int(np.asarray(jax.device_get(failed)).sum()) == 0
