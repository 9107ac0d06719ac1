"""The peak table, keyed by JAX's ``device_kind``; see ``peaks.json``."""

from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """``{"flops_bf16": ..., "hbm_bytes_per_s": ...}`` of one chip kind.

    A kind that is not in the table is an error, never another chip's
    numbers."""
    table = json.loads(TABLE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
