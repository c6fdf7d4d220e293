"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

TPU v5e (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect. A
device the table does not know is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float     # FLOP/s
    int8_ops: float       # OP/s
    hbm_bw: float         # bytes/s
    hbm_bytes: float      # bytes of device memory
    ici_bw: float         # bytes/s of one chip's whole interconnect

    def compute(self, tier: str) -> float:
        """Peak rate of the arithmetic a tier's projections run in."""
        return self.int8_ops if tier == "int8" else self.bf16_flops


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
                         hbm_bytes=16e9, ici_bw=1600e9 / 8),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
