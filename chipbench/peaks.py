"""Published peaks per device kind, used by every roofline and ``mfu``
reader.  A device kind that is not in the table is an error."""

from __future__ import annotations

from typing import Dict
from typing import NamedTuple


class Peak(NamedTuple):
    bf16_flops: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes
    source: str


_V5E = Peak(197e12, 819e9, 16e9,
            'Google Cloud documentation, "TPU v5e" (per chip)')

PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
