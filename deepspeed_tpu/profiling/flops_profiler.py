"""FLOPS profiler — XLA cost-analysis based.

Reference parity: ``deepspeed/profiling/flops_profiler/profiler.py:30``
(``FlopsProfiler``) and ``get_model_profile`` there. The reference
monkey-patches ``torch.nn.functional`` to count MACs module-by-module while
eager ops execute; on TPU the whole step is one compiled XLA program, so the
idiomatic source of truth is the compiler itself: ``jax.jit(fn).lower(...)
.compile().cost_analysis()`` reports exact flops / bytes-accessed for the
program XLA actually runs (post-fusion), and ``memory_analysis()`` reports
live-memory. Per-module breakdown comes from the parameter pytree (params per
top-level module) plus the analytic transformer cost model — the same
decomposition the reference prints, without perturbing the hot path.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import numpy as np

from deepspeed_tpu.utils.logging import logger

__all__ = [
    "FlopsProfiler",
    "get_model_profile",
    "profile_compiled",
    "number_to_string",
    "flops_to_string",
    "macs_to_string",
    "params_to_string",
    "duration_to_string",
]


# ---------------------------------------------------------------------------
# formatting helpers (reference profiler.py number_to_string family)
# ---------------------------------------------------------------------------

def number_to_string(num: float, units: Optional[str] = None,
                     precision: int = 2) -> str:
    if units is None:
        if num >= 1e12:
            return f"{num / 1e12:.{precision}f} T"
        if num >= 1e9:
            return f"{num / 1e9:.{precision}f} G"
        if num >= 1e6:
            return f"{num / 1e6:.{precision}f} M"
        if num >= 1e3:
            return f"{num / 1e3:.{precision}f} K"
        return f"{num:.{precision}f} "
    scale = {"T": 1e12, "G": 1e9, "M": 1e6, "K": 1e3, "": 1.0}[units]
    return f"{num / scale:.{precision}f} {units}"


def flops_to_string(flops: float, units=None, precision: int = 2) -> str:
    return number_to_string(flops, units, precision) + "FLOPS"


def macs_to_string(macs: float, units=None, precision: int = 2) -> str:
    return number_to_string(macs, units, precision) + "MACs"


def params_to_string(n: float, units=None, precision: int = 2) -> str:
    return number_to_string(n, units, precision).rstrip()


def bytes_to_string(n: float, precision: int = 2) -> str:
    return number_to_string(n, None, precision) + "B"


def duration_to_string(seconds: float, precision: int = 2) -> str:
    if seconds >= 1:
        return f"{seconds:.{precision}f} s"
    if seconds >= 1e-3:
        return f"{seconds * 1e3:.{precision}f} ms"
    return f"{seconds * 1e6:.{precision}f} us"


# ---------------------------------------------------------------------------
# compiled-program cost extraction
# ---------------------------------------------------------------------------

def profile_compiled(fn: Callable, *args, static_argnums=(),
                     **kwargs) -> Dict[str, float]:
    """Lower+compile ``fn`` and return XLA's cost analysis.

    Returns dict with keys ``flops``, ``bytes_accessed``, ``transcendentals``,
    ``peak_bytes`` (generated-code temp + output, when the backend reports
    memory analysis). Works on jitted or plain callables.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, static_argnums=static_argnums)
    lowered = jitted.lower(*args, **kwargs)
    compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    out = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "peak_bytes": 0.0,
    }
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            # donated inputs alias their outputs — counting both sides
            # double-books every donated buffer (ZeRO state is donated)
            out["peak_bytes"] = float(
                getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0))
    except Exception:  # backend may not implement memory analysis
        pass
    return out


def _count_params(tree) -> int:
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(tree)
                   if hasattr(x, "shape")))


def _per_module_params(params) -> Dict[str, int]:
    """Params per top-level pytree key (the 'module' granularity)."""
    if isinstance(params, dict):
        return {k: _count_params(v) for k, v in params.items()}
    return {"params": _count_params(params)}


# ---------------------------------------------------------------------------
# profiler
# ---------------------------------------------------------------------------

class FlopsProfiler:
    """Reference-parity profiler (profiler.py:30): ``start_profile`` /
    ``stop_profile`` / ``get_total_*`` / ``print_model_profile`` /
    ``end_profile``.

    Attach to an engine (``FlopsProfiler(engine=engine)``) to profile its
    compiled train step, or use standalone around any jittable fn via
    :func:`get_model_profile`.
    """

    def __init__(self, model=None, engine=None, config=None):
        self.model = model
        self.engine = engine
        self.config = config or (engine.config.flops_profiler
                                 if engine is not None else None)
        self.started = False
        self._t0 = 0.0
        self._duration = 0.0
        self._cost: Dict[str, float] = {}
        self._params_total = 0
        self._params_by_module: Dict[str, int] = {}

    # -- lifecycle ---------------------------------------------------------
    def start_profile(self, ignore_list=None):
        self.started = True
        self._t0 = time.time()
        if self.engine is not None:
            self._analyze_engine()
        elif self.model is not None and hasattr(self.model, "init"):
            params = self.model.abstract_params() if hasattr(
                self.model, "abstract_params") else None
            if params is not None:
                self._params_total = _count_params(params)
                self._params_by_module = _per_module_params(params)

    def stop_profile(self):
        if self.started:
            self._duration = time.time() - self._t0

    def end_profile(self):
        self.started = False

    def reset_profile(self):
        self._cost = {}
        self._duration = 0.0

    # -- engine analysis ---------------------------------------------------
    def _analyze_engine(self):
        eng = self.engine
        self._params_total = _count_params(eng.params)
        self._params_by_module = _per_module_params(eng.params)
        # cost of the compiled train step over one GAS window
        try:
            gas = eng.gradient_accumulation_steps
            batch = self._example_batch(gas)
            if batch is not None:
                self._cost = profile_compiled(
                    eng._jit_train_step, eng.params, eng.opt_state,
                    eng.loss_scale_state, eng.step_count, batch)
        except Exception as e:
            logger.debug(f"flops profiler: cost_analysis unavailable ({e})")

    def _example_batch(self, gas: int):
        eng = self.engine
        model = getattr(eng, "model", None)
        cfg = getattr(model, "config", None)
        if cfg is None or not hasattr(cfg, "max_seq_len"):
            return None
        import jax.numpy as jnp
        micro = eng.micro_batch_size * eng.dp_world_size  # global micro batch
        seq = min(cfg.max_seq_len, 512)
        tokens = jnp.zeros((gas, micro, seq), jnp.int32)
        batch = {"input_ids": tokens}
        return jax.device_put(batch, eng._batch_sharding(leading_dims=2))

    # -- totals (reference get_total_* API) --------------------------------
    def get_total_flops(self, as_string: bool = False):
        f = self._cost.get("flops", 0.0)
        return flops_to_string(f) if as_string else f

    def get_total_macs(self, as_string: bool = False):
        m = self._cost.get("flops", 0.0) / 2.0
        return macs_to_string(m) if as_string else m

    def get_total_params(self, as_string: bool = False):
        return (params_to_string(self._params_total) if as_string
                else self._params_total)

    def get_total_duration(self, as_string: bool = False):
        return (duration_to_string(self._duration) if as_string
                else self._duration)

    # -- report ------------------------------------------------------------
    def print_model_profile(self, profile_step: int = 1, module_depth: int = -1,
                            top_modules: int = 1, detailed: bool = True,
                            output_file: Optional[str] = None):
        lines = self._render(profile_step, detailed)
        text = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(text + "\n")
        else:
            print(text)

    def _render(self, profile_step: int, detailed: bool):
        lines = [
            "-" * 72,
            "DeepSpeed-TPU Flops Profiler",
            "-" * 72,
            f"Profile step:                   {profile_step}",
            f"Params:                         "
            f"{params_to_string(self._params_total)}",
        ]
        if self._cost:
            flops = self._cost["flops"]
            lines += [
                f"FLOPs per train step (XLA):     {flops_to_string(flops)}",
                f"MACs per train step:            "
                f"{macs_to_string(flops / 2)}",
                f"HBM bytes accessed:             "
                f"{bytes_to_string(self._cost['bytes_accessed'])}",
                f"Arithmetic intensity:           "
                f"{flops / max(self._cost['bytes_accessed'], 1):.1f} "
                f"FLOP/byte",
            ]
            if self._cost.get("peak_bytes"):
                lines.append(f"Compiled memory footprint:      "
                             f"{bytes_to_string(self._cost['peak_bytes'])}")
        if self._duration:
            lines.append(f"Profile duration:               "
                         f"{duration_to_string(self._duration)}")
            if self._cost:
                lines.append(
                    f"Achieved:                       "
                    f"{flops_to_string(self._cost['flops'] / self._duration)}")
        if detailed and self._params_by_module:
            lines.append("")
            lines.append("Per-module parameters:")
            total = max(self._params_total, 1)
            for name, n in sorted(self._params_by_module.items(),
                                  key=lambda kv: -kv[1]):
                lines.append(f"  {name:<28} {params_to_string(n):>10}  "
                             f"({100.0 * n / total:.1f}%)")
        lines.append("-" * 72)
        return lines


# ---------------------------------------------------------------------------
# standalone convenience (reference get_model_profile)
# ---------------------------------------------------------------------------

def get_model_profile(model, input_shape: Optional[Tuple[int, ...]] = None,
                      args=None, print_profile: bool = True,
                      detailed: bool = True, as_string: bool = True,
                      output_file: Optional[str] = None,
                      warm_up: int = 1) -> Tuple[Any, Any, Any]:
    """Profile one forward pass of ``model`` (reference
    ``flops_profiler/profiler.py`` ``get_model_profile``): returns
    ``(flops, macs, params)``.

    ``model`` is anything with ``.init(rng)`` + ``.apply(params, tokens)``
    (our zoo contract), or a plain callable when ``args`` is given.
    """
    import jax.numpy as jnp

    if hasattr(model, "init") and input_shape is not None:
        params = model.init(jax.random.PRNGKey(0))
        tokens = jnp.zeros(input_shape, jnp.int32)
        fn = lambda p, t: model.apply(p, t)
        cost = profile_compiled(fn, params, tokens)
        n_params = _count_params(params)
        by_module = _per_module_params(params)
    elif args is not None:
        cost = profile_compiled(model, *args)
        n_params = 0
        by_module = {}
    else:
        raise ValueError("need input_shape (zoo model) or args (callable)")

    prof = FlopsProfiler()
    prof._cost = cost
    prof._params_total = n_params
    prof._params_by_module = by_module
    if print_profile:
        prof.print_model_profile(detailed=detailed, output_file=output_file)
    flops, macs, n = cost["flops"], cost["flops"] / 2, n_params
    if as_string:
        return (flops_to_string(flops), macs_to_string(macs),
                params_to_string(n))
    return flops, macs, n
