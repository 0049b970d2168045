"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s). A kind that is not in the
table is an error, never a default: a roofline share or an MFU against
the wrong peak is a wrong number.
"""

from __future__ import annotations

import dataclasses

SOURCE = "Google Cloud documentation, TPU v5e (per chip)"


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # FLOP/s, MXU at bfloat16
    int8_ops: float         # OP/s, MXU at int8
    hbm_bytes: float        # bytes/s


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12,
                         hbm_bytes=819e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)}); add it to bench/peaks.py "
                       f"with its published source") from None
