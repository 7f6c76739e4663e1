"""Seconds per unit under the spans a CONTROLLER opens (the operator's
provisioning pass: ``provisioning.*`` in ``controllers/provisioning.py``, the
client's ``client.*`` under them), read as ``annotation_total`` reads the
served path's: from the profiler capture, where every span of the program is
a ``kc:<name>`` annotation on the capture's clock whatever thread or trace it
ran in — a controller's pass is a root trace of its own, outside the
handler's, so ``facts["units"]``' spans never hold it.  The same arithmetic
(``annotation_total.per_unit``), median over the capture's units.

spec: {"kind": "controller_span", "match": ["provisioning.launch"],
       "less": [...], "self": true}           as ``annotation_total``

A kind of its own for two reasons.  ``tests/benchmark_tests/
test_bench_annotations.py`` holds the ``annotation_total`` metrics to an exact
count and to the spans three files of the served path open, and no later PR
may edit it.  And this one reports in a rehearsal too: a rehearsal's line
then shows that each metric's spans are still opened under the names its
reader matches (a renamed span reads as nothing) — as a count of seconds on
a CPU it says nothing about speed, like every number of a rehearsal.  A
program without the spans (any commit before they existed) reads as nothing.
"""

from benchmark.harness import annotations, stats
from benchmark.harness.sources.annotation_total import per_unit


def read(spec: dict, facts: dict):
    capture = annotations.capture()
    values = per_unit(spec, capture) if capture else []
    return stats.median(values) if values else None
