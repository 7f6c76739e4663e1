"""A kernel's share of its roofline, in %: the least time the chip could take
for the work the kernel's shapes demand (``harness/roofline.py``) over the
device time the trace measured for one unit.

spec: {"kind": "roofline", "per_unit": "unit_busy_s"}
"""

from benchmark.harness import roofline
from benchmark.harness.sources import device_ops


def read(spec: dict, facts: dict):
    device_s = device_ops.read({"per_unit": spec["per_unit"]}, facts)
    if device_s is None or not facts.get("kernel_shapes") or not facts.get("peaks"):
        return None
    return roofline.share(facts["kernel_shapes"], device_s, facts["peaks"])["percent"]
