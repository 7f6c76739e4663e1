"""One number the harness counted or timed for the whole run.

spec: {"kind": "counter", "name": "compiles_in_window" | "backend_compiles" |
       "first_request_s" | "reply_pack_s" | "reply_unpack_s" | ...}
"""


def read(spec: dict, facts: dict):
    return facts["counters"].get(spec["name"])
