"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
JAX reports a v5e chip as ``TPU v5 lite``.  A device that is not in the
table is an error, never a default: a share of a peak that nobody looked up
is not a measurement.
"""
from __future__ import annotations

PEAKS = {"TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                         "source": "Google Cloud TPU v5e documentation"}}


class UnknownDevice(RuntimeError):
    pass


def peaks_for(device) -> dict:
    """The peaks of ``device`` (a ``jax.Device``); raises for anything that
    is not a TPU in the table, the CPU included."""
    if device.platform != "tpu":
        raise UnknownDevice(f"no peaks for platform {device.platform!r} "
                            f"({device.device_kind}); the benchmark needs a TPU")
    try:
        return PEAKS[device.device_kind]
    except KeyError:
        raise UnknownDevice(f"no published peaks for device kind "
                            f"{device.device_kind!r}; add it to "
                            "benchmarks/chip/peaks.py with its source") from None
