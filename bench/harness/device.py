"""The device a run is on: the chip check and the table of peaks.

Peaks are per chip, keyed by JAX's ``device_kind``. A kind that is not
in the table is an error, never a default.
"""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
# int8), 16 GB HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[kind]


def require_chips(chips: int) -> dict:
    """The device as JAX reports it, once it is known to be a TPU with
    at least ``chips`` chips of a kind in the table. Raises ``NoChip``
    otherwise."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no backend: {e}") from e
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX reports platform={info['platform']} "
                     f"kind={info['kind']}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees "
                     f"{info['count']}")
    peaks(info["kind"])
    return info


def memory(index: int = 0) -> dict:
    """``memory_stats()`` of device ``index`` ({} where the backend
    keeps none)."""
    import jax
    return jax.devices()[index].memory_stats() or {}
