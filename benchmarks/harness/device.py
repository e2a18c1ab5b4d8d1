"""The device as JAX reports it, and the table of published peaks.

A copy of the table in ``deepspeed_tpu/observability/roofline.py`` kept
with the yardstick, keyed by ``device_kind``; a device that is not in it
is an error, never a default. A measurement path that finds no TPU, or
another number of chips than the cell asks for, fails.
"""

from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 200e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


class NoChipError(RuntimeError):
    pass


class UnknownDeviceError(LookupError):
    pass


def describe() -> Dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require(chips: int, rehearse: bool) -> Dict:
    """The device record, or NoChipError. ``rehearse`` (the CPU tests and
    nothing else) lets a CPU through; its result line says ``cpu`` and
    carries no device metric."""
    dev = describe()
    if dev["platform"] != "tpu" and not rehearse:
        raise NoChipError(f"JAX found no TPU (platform {dev['platform']}): "
                          "nothing was measured")
    if dev["count"] != chips and not (rehearse and dev["count"] >= chips):
        raise NoChipError(f"the cell asks for {chips} chip(s), JAX sees "
                          f"{dev['count']} {dev['kind']}")
    return dev


def peaks(kind: str) -> Dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no published peaks for device kind {kind!r}; the table has "
            f"{sorted(PEAKS)}") from None


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest chip (0 where the backend reports
    none, as the CPU does)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
