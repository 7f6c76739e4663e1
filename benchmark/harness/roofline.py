"""A floor under the solve kernel's device time, from its shapes alone, and the
chip's peaks — the yardstick for ``solve_core_roofline``.

The solve (``ops/solve.py solve_core``) is mask algebra and comparisons over
integer and f32 planes: no formulation of it escapes reading its arguments and
writing its results, so the floor is memory traffic,

    bytes = in_bytes + carry_bytes + out_bytes

``in_bytes``     every array the program is given (``ops.solve.prepare_host``:
                 the class rows and the catalog's planes), read once; on several
                 chips the busiest reads at least its share, total / chips
``carry_bytes``  the final state it returns (``NodeState`` — ``viable``
                 bool[N, I] is most of it — topology counts, remaining budget),
                 written once, ON ONE CHIP (a catalog-sharded plane counts its
                 own slice)
``out_bytes``    the assignment planes, written once

and the least time is bytes / peak HBM bandwidth.  It is a floor for the
PROBLEM at these shapes, not a model of today's algorithm: the scan as written
carries its whole state through every class step (about passes x classes x 2 x
carry_bytes, which ``kernel_shapes`` lets anyone work out), and a kernel that
keeps the carry on the chip may come as close to this floor as it can — never
above 100 %.  No count of operations enters: how many capacity tests a solve
needs depends on the formulation.  Every size is read from one library solve's
own arrays; nothing here looks at the clock.
"""

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {_PEAKS}: add them "
            "with their source, never a default"
        )
    return table[device_kind]


def _chip_bytes(leaf) -> int:
    shards = getattr(leaf, "addressable_shards", None)
    return int(shards[0].data.nbytes if shards else getattr(leaf, "nbytes", 0))


def kernel_shapes(outputs, passes: int, in_bytes: int) -> dict:
    """The sizes ``floor`` needs, read from one solve's SolveOutputs and the
    bytes of its arguments."""
    import jax

    carry = (outputs.state, outputs.topo, outputs.remaining)
    viable = outputs.state.viable
    chips = len({s.device for s in viable.addressable_shards})
    return {
        "passes": max(int(passes), 1),
        "classes": int(outputs.assign.shape[0]),
        "slots": int(outputs.assign.shape[1]),
        "types_per_chip": int(viable.addressable_shards[0].data.shape[1]),
        "resources": int(outputs.state.used.shape[1]),
        "in_bytes": int(in_bytes) // chips,
        "carry_bytes": sum(_chip_bytes(x) for x in jax.tree_util.tree_leaves(carry)),
        "out_bytes": _chip_bytes(outputs.assign) + _chip_bytes(outputs.assign_existing),
    }


def floor(shapes: dict) -> int:
    """Bytes one chip cannot avoid moving."""
    return shapes["in_bytes"] + shapes["carry_bytes"] + shapes["out_bytes"]


def share(shapes: dict, device_s: float, peaks: dict) -> dict:
    """The floor's share of the measured device time, in %."""
    least = floor(shapes) / peaks["hbm_bytes_per_s"]
    return {"percent": 100.0 * least / device_s, "least_s": least, "bytes": floor(shapes)}
