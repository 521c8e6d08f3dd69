"""Published peaks of the chips the benchmark runs on, keyed by JAX's
`device_kind`.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 and 393 TOP/s int8 per chip, 16 GB of HBM2 at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect.  A kind that is not listed is an
error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bits_per_s": 1.6e12},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
