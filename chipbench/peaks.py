"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A device missing from ``peaks.json`` is an
error, never a default."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).with_name("peaks.json")


def peak(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PEAKS.name}; known: "
                       f"{sorted(table['devices'])}") from None
