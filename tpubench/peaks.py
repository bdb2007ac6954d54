"""The table of peaks, and what one forward must read.

Peaks are the published figures of the chip, keyed by the
``device_kind`` JAX reports. A device that is not in the table is an
error, never a default: a utilization against the wrong peak is worse
than none. (The program keeps a table of its own in
``tpushare/utils/profiling.py``; this copy is the yardstick's, out of
reach of a PR that edits the program.)
"""

from __future__ import annotations

from typing import Any, Dict

from tpubench import spec

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
#: int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s interconnect per chip.
PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2 ** 30,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind: str) -> Dict[str, Any]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"device kind {device_kind!r} is not in tpubench/peaks.py; "
            f"known: {sorted(PEAKS)}. Add its published peaks with their "
            f"source; there is no default.") from None


DTYPE_BYTES = {"bfloat16": 2, "float32": 4, "float16": 2}


def forward_weight_bytes(config: Dict[str, Any]) -> int:
    """Bytes of weights one forward must read from HBM: the count of the
    configuration's family (``families/<family>.py``), from the shapes
    alone."""
    return spec.family(config["family"]).forward_weight_bytes(config)
