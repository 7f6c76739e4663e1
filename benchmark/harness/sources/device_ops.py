"""Device time from the profiler trace's reduction (``xplane.reduce``).

spec: {"kind": "device_ops", "per_unit": "unit_busy_s" | "unit_program_s"}
        median over the traced units of the chip-averaged seconds
      {"kind": "device_ops", "share": "collective_s", "of": "busy_s"}
        one total over another, in %
"""

from benchmark.harness import stats


def read(spec: dict, facts: dict):
    device = facts["device"]
    if device is None:
        return None
    if "per_unit" in spec:
        values = [v for v in device[spec["per_unit"]] if v > 0]
        return stats.median(values) if values else None
    whole = device[spec["of"]]
    return 100.0 * device[spec["share"]] / whole if whole else None
