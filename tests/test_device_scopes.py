"""The kernel's ``jax.named_scope`` names: free, and all there.

``ops/solve.py`` and ``ops/consolidate.py`` open a ``kc.<name>`` scope on each
block of the program (docs/OBSERVABILITY.md "Scopes in a profiler capture");
the benchmark's ``kernel_*_s`` metrics read device time by those names from a
profiler capture.  Held here, by LOWERING alone (no compile: the retrace
budget counts those):

- a scope adds no operation: the lowered module with debug info off is the
  same text with ``jax.named_scope`` and with ``contextlib.nullcontext`` in
  its place, for the plain build, the existing-nodes build, the build whose
  step goes by row and the consolidation sweep's ``vmap`` — so the scopes can
  re-key no XLA executable and cost nothing with tracing off;
- every name of the contract is in the lowering with debug info on, for the
  build that traces it, also after the export cache's round trip
  (``export`` -> ``serialize`` -> ``deserialize`` -> ``jit(exported.call)``).
"""

import contextlib
import functools
import re

import jax
import numpy as np
import pytest

import test_topo_rows as rows
from karpenter_core_tpu.ops import consolidate as consolidate_ops
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.utils import compilecache

STEP = {
    "kc.init", "kc.scan", "kc.step.derive", "kc.step.prep_existing", "kc.step.zone_intake",
    "kc.phase.zone_spread", "kc.phase.zone_anti", "kc.phase.zone_affinity",
    "kc.phase.host_affinity", "kc.phase.plain", "kc.existing", "kc.new", "kc.committal",
    "kc.fill", "kc.step.record", "kc.finish",
}
# build -> (test_topo_rows seed, the names its lowering must hold); every
# build is lowered with ALL_FEATURES, so each traces every phase family
BUILDS = {
    "plain": (1, STEP),
    "existing": (0, STEP),
    "by_row": (10, STEP),
    "sweep": (0, STEP | {"kc.sweep.seed", "kc.sweep.reduce"}),
}
NAME = re.compile(r"kc\.[\w.]+")


def struct(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype), tree)


@functools.lru_cache(maxsize=None)
def prepared(seed: int):
    """(snapshot, SolvePrep) of one of test_topo_rows' fuzzed batches: seed 0
    has a live cluster of three nodes, seed 10 groups enough to go by row."""
    env = rows.fuzz_environment(seed)
    solver = TPUSolver(env.provider, env.kube.list_provisioners())
    state_nodes, bound = env.cluster.snapshot_nodes(), env.kube.list_pods()
    snapshot = solver.encode(rows.fuzz_batch(seed), state_nodes, bound)
    return snapshot, solver.prepare_encoded(snapshot, state_nodes, bound)


def traced(build: str):
    """``(function, argument structs)`` of the build's program."""
    snapshot, prep = prepared(BUILDS[build][0])
    core = functools.partial(
        solve_ops.solve_core, n_slots=prep.n_slots, key_has_bounds=prep.key_has_bounds,
        n_passes=1)
    if build == "sweep":
        n_classes, n_ex = np.shape(prep.ex_static.tol)

        def sweep(cls, statics, ex_state, ex_static, rank, counts, sizes, price):
            return consolidate_ops.sweep(
                cls, statics, prep.key_has_bounds, ex_state, ex_static, rank, counts,
                sizes, price, n_slots=prep.n_slots, n_passes=1)

        return sweep, struct((
            prep.cls, prep.statics_arrays, prep.ex_state, prep.ex_static,
            np.zeros(n_ex, np.int32), np.zeros((n_classes, n_ex), np.int32),
            np.zeros(2, np.int32), np.asarray(snapshot.it_price)))
    if prep.ex_state is None:
        return (lambda cls, statics: core(cls, statics)), struct((prep.cls, prep.statics_arrays))
    return (
        lambda cls, statics, ex_state, ex_static: core(
            cls, statics, existing_state=ex_state, existing_static=ex_static),
        struct((prep.cls, prep.statics_arrays, prep.ex_state, prep.ex_static)),
    )


def text(lowered, debug_info: bool) -> str:
    return lowered.compiler_ir(dialect="stablehlo").operation.get_asm(
        enable_debug_info=debug_info)


@functools.lru_cache(maxsize=None)
def lowered(build: str):
    fn, args = traced(build)
    return jax.jit(fn).lower(*args)


def test_the_builds_are_the_four_the_contract_names():
    for build, (seed, _) in BUILDS.items():
        prep = prepared(seed)[1]
        assert (prep.ex_state is not None) == (build in ("existing", "sweep")), build
        assert rows.goes_by_row(prep) == (build == "by_row"), build


@pytest.mark.parametrize("build", sorted(BUILDS))
def test_a_scope_adds_no_operation(build, monkeypatch):
    with_scopes = text(lowered(build), debug_info=False)
    fn, args = traced(build)
    monkeypatch.setattr(jax, "named_scope", contextlib.nullcontext)
    without = jax.jit(fn).lower(*args)
    assert not NAME.search(text(without, debug_info=True))  # the patch took
    assert text(without, debug_info=False) == with_scopes


@pytest.mark.parametrize("build", sorted(BUILDS))
def test_every_name_is_in_the_lowering(build):
    found = set(NAME.findall(text(lowered(build), debug_info=True)))
    assert found >= BUILDS[build][1], sorted(BUILDS[build][1] - found)
    # the names are the contract: a new one is added to it here, on purpose
    assert found <= BUILDS["sweep"][1], sorted(found - BUILDS["sweep"][1])


@pytest.mark.parametrize("build", ("plain", "sweep"))
def test_the_names_survive_the_export_cache(build):
    fn, args = traced(build)
    compilecache.enable()  # the kernel's pytree types, as the cache registers them
    blob = jax.export.export(jax.jit(fn))(*args).serialize()
    again = jax.jit(jax.export.deserialize(blob).call).lower(*args)
    found = set(NAME.findall(text(again, debug_info=True)))
    assert found >= BUILDS[build][1], sorted(BUILDS[build][1] - found)


def test_the_small_programs_of_the_served_path_carry_a_name_too():
    """The warm repair's three jits and the answer's ``pack_bool``: programs
    of their own in a capture, which would read as unscoped."""
    _, prep = prepared(1)
    fn, args = traced("plain")
    carry = solve_ops.warm_carry_of(jax.eval_shape(fn, *args))
    n_classes = np.shape(prep.cls.count)[0]
    g1 = carry.topo.fwd_new.shape[0]
    n_slots, n_ex = carry.state.pod_count.shape[0], carry.ex_state.pod_count.shape[0]
    idx = jax.ShapeDtypeStruct((8,), np.int32)
    window, _ = jax.eval_shape(solve_ops.gather_repair_window, carry, idx, 4)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, np.int32)  # noqa: E731
    programs = {
        "kc.repair.gather": solve_ops.gather_repair_window.lower(carry, idx, 4),
        "kc.repair.scatter": solve_ops.scatter_repair_window.lower(carry, window, idx, 4),
        "kc.repair.free": solve_ops.repair_free.lower(
            carry, i32(n_classes, n_slots), i32(n_classes, n_ex),
            jax.ShapeDtypeStruct(np.shape(prep.cls.requests), np.float32),
            i32(n_classes, g1), i32(n_classes, g1)),
        "kc.finish": solve_ops.pack_bool.lower(carry.state.viable),
    }
    for name, program in programs.items():
        assert set(NAME.findall(text(program, debug_info=True))) == {name}
