"""The chip: the refusal to run anywhere else, the table of peaks, and
what JAX reports about the devices.

``require_tpu`` is copied from the repository's bring-up check
(``chip_smoke.py``) so that a later change there cannot move it.
"""
from __future__ import annotations

# Peaks per chip, keyed by jax's ``device_kind``.  Source: Google Cloud
# documentation, "TPU v5e" (system architecture page): 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def require_tpu(count: int):
    """The TPU devices this run holds; exits when JAX finds fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < count:
        raise SystemExit(
            f"bench: needs {count} TPU device(s); JAX found "
            f"{len(devs)} {devs[0].platform} device(s). Not running on "
            f"anything else.")
    return devs


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(devs) -> int:
    """peak_bytes_in_use of the fullest device (0 where not reported)."""
    peak = 0
    for d in devs:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def memory_limit(devs) -> int:
    st = devs[0].memory_stats() or {}
    return int(st.get("bytes_limit", 0))
