"""The chip the run stands on, and its published peaks.

The peak table is keyed by ``device_kind`` as JAX reports it.  A kind
that is not in it is an error, never a default, and a run that finds no
TPU, or fewer chips than its cell asks for, fails before it measures.
"""

from __future__ import annotations

import jax

#: peaks of one chip: bf16 matmul FLOP/s and HBM bytes/s
PEAKS = {
    "TPU v5 lite": dict(
        bf16_flops=197e12, hbm_bytes=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


class NoChip(RuntimeError):
    """No TPU, too few chips, or a chip whose peaks are not known."""


def require(chips: int) -> list:
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise."""
    try:
        devs = jax.devices()
    except RuntimeError as err:
        raise NoChip(f"JAX finds no accelerator: {err}") from err
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"{len(devs)} chips, the cell asks for {chips}")
    if devs[0].device_kind not in PEAKS:
        raise NoChip(f"no peaks known for {devs[0].device_kind!r}")
    return devs[:chips]


def describe(devs: list) -> dict:
    """The result line's ``device``: as JAX reports it, with the peak
    bytes in use on the fullest chip (where the backend counts them)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), memory_peak_bytes=max(peaks))
