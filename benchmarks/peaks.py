"""The table of peaks: the yardstick's numbers.

One row: a TPU v5e chip, which JAX reports as ``TPU v5 lite``. Source:
Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
bf16, 819 GB/s of HBM bandwidth, 16 GB of HBM per chip. A device that
is not in the table is an error, not a default.

The operations and bytes of a row are its family's to count
(``benchmarks/families/<name>.py``: ``flops_per_row``,
``wire_bytes_per_row``).
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peak_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind.strip()]
    except KeyError:
        raise KeyError("device_kind %r is not in benchmarks/peaks.py "
                       "(%s): a rate against an unknown peak is not "
                       "reported" % (device_kind, sorted(PEAKS))) from None
