"""Published per-chip peaks, keyed by ``device_kind`` (peaks.json). A
device that is not in the table is an error, never a default."""
from __future__ import annotations

from typing import Dict

from harness.spec import BENCH_DIR, load_json


class UnknownDevice(KeyError):
    pass


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(BENCH_DIR / "harness" / "peaks.json")["devices"]
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r}; "
                            f"the table has {sorted(table)}")
    return table[device_kind]
