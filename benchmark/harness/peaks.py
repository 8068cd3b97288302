"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it. A device that is not in the table is an
error, never a default (and there is no ``cpu`` row: a CPU run has no device
metric)."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,      # FLOP/s, bf16 matmul
        "hbm_bytes_s": 819e9,      # B/s
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e'",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> Dict[str, object]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to benchmark/harness/peaks.py") from None
