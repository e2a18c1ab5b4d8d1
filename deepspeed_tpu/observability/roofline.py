"""Peak-rate tables, MFU, and roofline classification.

Single home for the chip peak numbers (bench.py imports from here so the
engine-reported MFU and the benchmark headline are computed from the
same table and the same formula — the 2%-agreement contract in
tests/test_observability.py). Roofline math follows docs/roofline.md:
arithmetic intensity from XLA's compiled-program cost analysis
(flops / bytes accessed) against the chip's ridge point.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

# Peaks are keyed by a substring of ``device.device_kind`` (a v5e chip
# reports "TPU v5 lite"). Source: Google Cloud TPU documentation, the
# system-architecture page of each generation ("TPU v4", "TPU v5e",
# "TPU v5p", "TPU v6e"). A device that is not in the tables is an error,
# never a default: a number computed against the wrong chip is worse
# than no number.

# per-chip dense bf16 peak TFLOPS by TPU generation
PEAK_TFLOPS = {
    "v4": 275.0,
    "v5 lite": 197.0,  # v5e
    "v5e": 197.0,
    "v5p": 459.0,
    "v6 lite": 918.0,  # v6e (Trillium)
    "v6e": 918.0,
}

# per-chip HBM bandwidth, GB/s (public TPU system specs)
HBM_GBPS = {
    "v4": 1228.0,
    "v5 lite": 819.0,
    "v5e": 819.0,
    "v5p": 2765.0,
    "v6 lite": 1640.0,
    "v6e": 1640.0,
}

class UnknownDeviceError(LookupError):
    """The device is in no peak table and no override was given."""


def _lookup(table: Dict[str, float], env_key: str, device, what: str
            ) -> float:
    if env_key in os.environ:
        return float(os.environ[env_key])
    kind = getattr(device, "device_kind", "")
    for key, val in table.items():
        if key in kind.lower():
            return val
    raise UnknownDeviceError(
        f"no {what} for device_kind {kind!r}: add the chip to "
        f"observability/roofline.py with its source, or pass {env_key}")


def detect_peak_tflops(device) -> float:
    """bf16 peak for ``device``; BENCH_PEAK_TFLOPS env overrides.
    Raises :class:`UnknownDeviceError` for a device not in the table."""
    return _lookup(PEAK_TFLOPS, "BENCH_PEAK_TFLOPS", device,
                   "bf16 peak TFLOP/s")


def detect_hbm_gbps(device) -> float:
    """HBM bandwidth for ``device``; BENCH_HBM_GBPS env overrides.
    Raises :class:`UnknownDeviceError` for a device not in the table."""
    return _lookup(HBM_GBPS, "BENCH_HBM_GBPS", device, "HBM GB/s")


CPU_SMOKE_ENV = "BENCH_CPU_SMOKE"


class NoChipError(RuntimeError):
    """A measurement path found no TPU and no CPU smoke was asked for."""


def on_tpu_or_named_cpu_smoke() -> bool:
    """True on a TPU backend. Anywhere else a benchmark may run only as
    the CPU smoke its caller asked for by name (``BENCH_CPU_SMOKE=1``:
    toy shapes, for control flow and counts, labelled cpu) — then this
    returns False. Without that it raises: a time or a rate taken off
    the chip is not a result, so nothing prints one by accident."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return True
    if os.environ.get(CPU_SMOKE_ENV) == "1":
        return False
    raise NoChipError(
        f"backend is {backend!r}, not a TPU: benchmarks report device "
        f"numbers and refuse to run elsewhere. Set {CPU_SMOKE_ENV}=1 for "
        "the toy-size CPU smoke (control flow and counts only)")


def mfu(tokens_per_sec_per_chip: float, flops_per_token: float,
        peak_tflops: float) -> float:
    """Model-FLOPs utilization — bench.py's exact formula."""
    if peak_tflops <= 0:
        return 0.0
    return tokens_per_sec_per_chip * flops_per_token / (peak_tflops * 1e12)


def roofline_summary(cost: Dict[str, float], peak_tflops: float,
                     hbm_gbps: float,
                     step_seconds: Optional[float] = None
                     ) -> Dict[str, Any]:
    """Classify a compiled program against the chip roofline.

    ``cost`` is XLA cost analysis output ({"flops", "bytes_accessed",
    ...}, see utils/hlo_bytes.program_costs). Returns arithmetic
    intensity, the chip ridge point, which side of it the program sits
    on, the attainable TFLOPS ceiling, and — when ``step_seconds`` is
    given — the achieved TFLOPS and fraction of attainable.
    """
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes_accessed", 0.0))
    intensity = flops / bytes_accessed if bytes_accessed > 0 else float("inf")
    ridge = peak_tflops * 1e12 / (hbm_gbps * 1e9)  # FLOPs per HBM byte
    bound = "compute" if intensity >= ridge else "memory"
    attainable = (peak_tflops if bound == "compute"
                  else hbm_gbps * intensity / 1e3)  # GB/s * F/B -> TFLOPS
    out = {
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "arithmetic_intensity": round(intensity, 3),
        "ridge_intensity": round(ridge, 3),
        "bound": bound,
        "peak_tflops": peak_tflops,
        "hbm_gbps": hbm_gbps,
        "attainable_tflops": round(attainable, 3),
    }
    if step_seconds and step_seconds > 0:
        achieved = flops / step_seconds / 1e12
        out["achieved_tflops"] = round(achieved, 4)
        out["hw_flops_utilization"] = round(achieved / peak_tflops, 4)
        if attainable > 0:
            out["fraction_of_attainable"] = round(achieved / attainable, 4)
    return out
