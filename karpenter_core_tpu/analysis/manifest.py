"""The retrace-budget manifest: the loader of the checked-in file.

tests/conftest.py (per-test budget enforcement) reads it through here;
keeping the path and the degrade-to-empty error policy in one place means
moving or re-shaping the manifest is a one-file edit.  Stdlib-only and safe
to import before any backend decision.
"""

from __future__ import annotations

import json
import os

MANIFEST_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "retrace_budget.json"
)


def load_retrace_manifest() -> dict:
    """The parsed manifest, or {} when missing/unreadable — budget checks
    degrade to advisory-off rather than breaking the caller."""
    try:
        with open(MANIFEST_PATH) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}
