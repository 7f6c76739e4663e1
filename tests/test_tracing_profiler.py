"""Spans on the profiler's clock: with tracing on, every span holds a
``jax.profiler.TraceAnnotation`` named ``kc:<span name>`` for its life
(docs/OBSERVABILITY.md "Spans in a profiler capture"); with tracing off,
``tracing.span()`` enters nothing."""

import glob
import os
import subprocess
import sys

import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.tracing import trace as trace_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Counting:
    """Stands in for the resolved annotation class: counts what is entered
    and left, by name."""

    entered: list = []
    left: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        Counting.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        Counting.left.append(self.name)
        return False


@pytest.fixture()
def counting(monkeypatch):
    Counting.entered, Counting.left = [], []
    monkeypatch.setattr(trace_mod, "_annotation", Counting)
    return Counting


def test_disabled_span_enters_no_annotation(counting):
    assert not tracing.enabled()
    with tracing.span("quiet"):
        with tracing.span_remote("quiet.remote", {"traceId": "ab", "spanId": "cd"}):
            pass
    assert counting.entered == [] and counting.left == []


def test_enabled_span_holds_a_prefixed_annotation_for_its_life(counting, traced):
    with tracing.span("outer"):
        assert counting.entered == ["kc:outer"] and counting.left == []
        with tracing.span("inner"):
            assert counting.entered == ["kc:outer", "kc:inner"]
        assert counting.left == ["kc:inner"]
    assert counting.left == ["kc:inner", "kc:outer"]


def test_span_that_raises_closes_its_annotation(counting, traced):
    with pytest.raises(KeyError):
        with tracing.span("boom"):
            raise KeyError("gone")
    assert counting.entered == ["kc:boom"] and counting.left == ["kc:boom"]
    # and the store still has the span, with the error on it
    assert "KeyError" in tracing.TRACE_STORE.last(1)[0].spans[0]["attrs"]["error"]


@pytest.mark.parametrize("ctx", [{"traceId": "feedface00000000", "spanId": "0badcafe"}, None])
def test_span_remote_annotates(counting, traced, ctx):
    with tracing.span_remote("solve.tenant", ctx) as sp:
        assert counting.entered == ["kc:solve.tenant"]
    assert counting.left == ["kc:solve.tenant"]
    if ctx:
        assert sp.trace_id == ctx["traceId"] and sp.parent_id == ctx["spanId"]


def test_annotation_outlives_the_sync_wait(counting, traced):
    """The span's duration includes the block on its ``sync`` target, so the
    annotation is left only after it."""
    order = []

    class Target:
        def block_until_ready(self):
            order.append(("blocked", list(counting.left)))
            return self

    with tracing.span("solve", sync=Target()):
        pass
    assert order == [("blocked", [])]
    assert counting.left == ["kc:solve"]


def test_the_class_is_resolved_once_and_is_jax_s(monkeypatch, traced):
    import jax.profiler

    monkeypatch.setattr(trace_mod, "_annotation", None)
    with tracing.span("first"):
        pass
    assert trace_mod._annotation is jax.profiler.TraceAnnotation


def test_trace_module_imports_and_spans_without_jax():
    """``tracing/trace.py`` imports without JAX at module level, a disabled
    span imports nothing, and where JAX cannot be imported at all an enabled
    span still records (and annotates nothing)."""
    code = (
        "import sys\n"
        "from karpenter_core_tpu.tracing import trace\n"
        "assert 'jax' not in sys.modules, 'import pulled in jax'\n"
        "with trace.span('off'):\n"
        "    pass\n"
        "assert 'jax' not in sys.modules and trace._annotation is None\n"
        "sys.modules['jax'] = None  # any import of jax now raises ImportError\n"
        "sys.modules['jax.profiler'] = None\n"
        "trace.enable()\n"
        "with trace.span('on'):\n"
        "    pass\n"
        "assert trace.TRACE_STORE.last(1)[0].name == 'on'\n"
        "import contextlib\n"
        "assert trace._annotation is contextlib.nullcontext\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "KC_TRACE"}
    env["PYTHONPATH"] = REPO
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]


def test_spans_land_on_the_host_plane_of_a_capture(tmp_path, traced):
    """A ``jax.profiler`` capture around two nested spans shows both as
    ``kc:`` events on ``/host:CPU``, the inner inside the outer, on the
    capture's own clock."""
    import jax
    import jax.numpy as jnp
    import jax.profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tracing.span("outer.phase", pods=3):
            with tracing.span("inner.phase"):
                jax.block_until_ready(jnp.arange(8) * 2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("kc:"):
                    found[e.name] = (plane.name, line.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
    assert set(found) == {"kc:outer.phase", "kc:inner.phase"}
    outer, inner = found["kc:outer.phase"], found["kc:inner.phase"]
    assert outer[0] == inner[0] == "/host:CPU"
    assert outer[1] == inner[1]  # one thread ran both
    assert outer[2] <= inner[2] and inner[3] <= outer[3]
