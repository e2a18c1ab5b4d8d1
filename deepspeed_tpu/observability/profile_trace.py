"""On-demand jax.profiler trace capture, gated by env var.

``DSTPU_TRACE_STEPS=5:8`` makes the engine capture an xplane trace of
global steps 5 through 8 (inclusive; a single number traces that one
step) into ``DSTPU_TRACE_DIR`` (default ``/tmp/dstpu_trace``) — open it
with TensorBoard's profile plugin or xprof. No code change, no restart
with different flags: the window is checked against the engine's step
counter at the train_batch boundary, so a long run can be profiled by
setting the env var before launch and letting the window pass.

What the capture shows by name (docs/observability.md, "Profiler spans
and names"): each step is a ``dstpu/train_batch`` step span with the
host phases nested in it (``utils/annotate.py``; the engine opens them
whether or not a capture runs, a profiler session is what records
them), the device programs are ``jit_dstpu_*`` modules, the Pallas
kernels carry their ``name=``, and ``forward_backward`` / ``optimizer``
/ ``attn`` / ``mlp`` are ``jax.named_scope`` regions of the HLO.
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

from deepspeed_tpu.utils.logging import logger


def parse_trace_steps(spec: str) -> Optional[Tuple[int, int]]:
    """'5:8' -> (5, 8); '12' -> (12, 12); '' / malformed -> None."""
    spec = (spec or "").strip()
    if not spec:
        return None
    try:
        if ":" in spec:
            a, b = spec.split(":", 1)
            lo, hi = int(a), int(b)
        else:
            lo = hi = int(spec)
        if lo < 0 or hi < lo:
            raise ValueError(spec)
        return lo, hi
    except ValueError:
        logger.warning(
            f"DSTPU_TRACE_STEPS={spec!r} not understood (want 'N' or "
            "'LO:HI'); trace capture disabled")
        return None


class TraceCapture:
    """Start/stop ``jax.profiler`` around a step window."""

    def __init__(self, window: Optional[Tuple[int, int]] = None,
                 out_dir: Optional[str] = None):
        self.window = window
        self.out_dir = out_dir or os.environ.get("DSTPU_TRACE_DIR",
                                                 "/tmp/dstpu_trace")
        self.active = False
        self.done = False
        self._stop_due = False

    @classmethod
    def from_env(cls) -> "TraceCapture":
        return cls(window=parse_trace_steps(
            os.environ.get("DSTPU_TRACE_STEPS", "")))

    @property
    def enabled(self) -> bool:
        return self.window is not None and not self.done

    def on_step_begin(self, step: int) -> None:
        """Call with the 1-based index of the step about to run."""
        if not self.enabled:
            return
        lo, hi = self.window
        if not self.active and lo <= step <= hi:
            import jax

            try:
                os.makedirs(self.out_dir, exist_ok=True)
                jax.profiler.start_trace(self.out_dir)
                self.active = True
                logger.warning(
                    f"profiler trace started at step {step} "
                    f"(window {lo}:{hi}) -> {self.out_dir}")
            except Exception as e:
                logger.warning(f"profiler trace start failed: {e}")
                self.done = True

    def on_step_end(self, step: int) -> None:
        """Call when a step's results have resolved. The session closes
        in ``stop_if_due``, once the caller has left the step's own
        ``dstpu/train_batch`` span: a span that ends after the session
        is lost."""
        if self.active and step >= self.window[1]:
            self._stop_due = True

    def stop_if_due(self) -> None:
        if not (self.active and self._stop_due):
            return
        import jax

        try:
            jax.profiler.stop_trace()
            logger.warning(
                f"profiler trace stopped after step {self.window[1]}; "
                f"view with `tensorboard --logdir {self.out_dir}` "
                "(profile tab)")
        except Exception as e:
            logger.warning(f"profiler trace stop failed: {e}")
        self.active = False
        self.done = True   # one capture per process
