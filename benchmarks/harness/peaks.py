"""The benchmark's own table of published peaks, keyed by ``device_kind``.
A device that is not in the table is an error, never a default."""

from __future__ import annotations

import json
import os
from typing import Dict

from .catalog import BenchError

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> Dict[str, float]:
    with open(_PATH) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise BenchError(
            f"device_kind {device_kind!r} is not in the benchmark's peak "
            f"table ({', '.join(sorted(table))}); add it to {_PATH} with "
            f"its source")
    return table[device_kind]
