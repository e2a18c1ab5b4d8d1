"""Communication facade: named-axis collectives over ICI/DCN.

TPU-native analog of ``deepspeed.comm`` (reference: deepspeed/comm/comm.py —
the torch.distributed-shaped module API at :227-682, ``init_distributed``
:792, ``timed_op`` wrappers :106). Three deltas from the reference design:

  1. There is no backend zoo (NCCL/gloo/CCL/...) — XLA emits the collectives
     for the platform; the "backend" is the compiler. Capability probes like
     ``has_all_gather_into_tensor`` become trivially true.
  2. Collectives are *named-axis* ops usable inside jit/shard_map bodies
     (they wrap ``jax.lax`` primitives). Outside jit, GSPMD usually inserts
     them from sharding annotations and user code never calls these.
  3. Per-op logging happens at trace time (see utils/comms_logging.py),
     because timing individual ops inside a compiled program from Python is
     meaningless.

``init_distributed`` performs the multi-host rendezvous
(``jax.distributed.initialize``), the analog of joining the job-wide
process group the reference launcher creates (comm/comm.py:792 →
torch.distributed.init_process_group).
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.utils.comms_logging import get_comms_logger
from deepspeed_tpu.utils.logging import log_dist, logger

__all__ = [
    "init_distributed", "is_initialized", "get_world_size", "get_rank",
    "get_local_rank", "get_process_count", "barrier",
    "assert_same_across_processes", "any_process",
    "has_all_gather_into_tensor", "has_reduce_scatter_tensor",
    "has_coalescing_manager", "all_reduce", "all_gather", "reduce_scatter",
    "all_to_all", "ppermute", "broadcast", "axis_index", "axis_size",
    "traced_span", "configure", "log_summary", "get_retry_policy",
]

_INITIALIZED = False

# -- control-plane health (resilience block; docs/resilience.md) -------------
# A RetryPolicy bounds the process-level ops a wedged peer turns into a
# silent fleet-wide hang: rendezvous init, barrier, cross-process asserts.
# With no `resilience` config applied the default policy has no timeouts
# and every op is a plain passthrough.
_POLICY = None


def get_retry_policy():
    """The active control-plane RetryPolicy (timeout-less until
    ``configure`` installs one from the ``resilience`` config block)."""
    global _POLICY
    if _POLICY is None:
        from deepspeed_tpu.resilience.policy import RetryPolicy

        _POLICY = RetryPolicy()
    return _POLICY


def _chaos_collective(op: str) -> None:
    """Chaos hook: lets DSTPU_CHAOS delay/fail the Kth control-plane op
    (injected ChaosCollectiveError propagates; everything else is inert)."""
    try:
        from deepspeed_tpu.resilience.chaos import get_chaos_injector

        inj = get_chaos_injector()
    except Exception:
        return
    if inj.armed:
        inj.on_collective(op)


def is_initialized() -> bool:
    return _INITIALIZED


def init_distributed(
    dist_backend: str = "xla",
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    timeout: Optional[int] = None,
    dist_init_required: Optional[bool] = None,
) -> None:
    """Join the multi-host rendezvous (analog of comm/comm.py:792).

    Single-host (or already-initialized) is a no-op. Multi-host parameters
    come from args or the standard env autodiscovery the reference performs
    (comm/comm.py:861-953): COORDINATOR_ADDRESS / NUM_PROCESSES / PROCESS_ID,
    plus TPU pod metadata which jax.distributed discovers natively.
    """
    global _INITIALIZED
    if _INITIALIZED or dist_init_required is False:
        return
    coordinator_address = coordinator_address or os.environ.get(
        "COORDINATOR_ADDRESS") or os.environ.get("JAX_COORDINATOR_ADDRESS")
    num_processes = num_processes or _env_int("NUM_PROCESSES")
    process_id = process_id if process_id is not None else _env_int("PROCESS_ID")
    policy = get_retry_policy()
    try:
        # Only rendezvous when multi-host is explicitly configured; never
        # infer from TPU_* env alone (single-host sandboxes set those).
        if coordinator_address or (num_processes or 0) > 1 or dist_init_required:
            # bounded by resilience.init_timeout_s: a peer that never
            # shows up at rendezvous becomes a typed CommTimeoutError
            # (transient exit code) instead of an indefinite hang
            policy.run(
                "init_distributed",
                lambda: jax.distributed.initialize(
                    coordinator_address=coordinator_address,
                    num_processes=num_processes,
                    process_id=process_id,
                ),
                timeout_s=policy.init_timeout_s,
            )
            log_dist(
                f"initialized distributed runtime: {jax.process_count()} processes",
                ranks=[0],
            )
    except RuntimeError as e:
        from deepspeed_tpu.resilience.policy import CommTimeoutError

        if isinstance(e, CommTimeoutError):
            raise  # exhausted rendezvous deadline — not "already init'd"
        # already initialized by the launcher — fine
        logger.debug(f"jax.distributed.initialize skipped: {e}")
    _INITIALIZED = True


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v is not None else None


# -- world/rank queries (process granularity on TPU) ------------------------


def get_world_size(group: Any = None) -> int:
    """Total **device** count (the reference's world = one rank per device).

    NOTE the granularity split vs the reference: on TPU one controller
    process drives many devices, so there is no per-device Python rank.
    ``get_world_size`` is device-granular (matches comm-volume math);
    ``get_rank`` is process-granular (matches "who does host-side work").
    Reference-style ``rank == world_size - 1`` loops do not port; use
    mesh-axis logic (lax.axis_index) inside compiled code instead.
    """
    return jax.device_count()


def get_rank(group: Any = None) -> int:
    """Host **process** index (see granularity note on get_world_size)."""
    return jax.process_index()


def get_local_rank() -> int:
    return 0  # one controller process per host drives all local devices


def get_process_count() -> int:
    return jax.process_count()


def barrier(group: Any = None) -> None:
    """Cross-host barrier: tiny psum over all devices. Bounded by
    ``resilience.collective_timeout_s`` when configured — a peer that
    never arrives raises CommTimeoutError instead of hanging the host."""
    _chaos_collective("barrier")
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        get_retry_policy().run(
            "barrier",
            lambda: multihost_utils.sync_global_devices(
                "deepspeed_tpu.barrier"))


def assert_same_across_processes(name: str, values) -> None:
    """Fail loudly when a config-critical value diverges across hosts.

    Reference: ``assert_ints_same_as_other_ranks`` (runtime/zero/
    utils.py:106) and AutoEP's cross-rank payload digests
    (moe/ep_tp_dispatch.py:99) — multi-host divergence (mismatched
    configs, different checkpoints, skewed data pipelines) otherwise
    corrupts training silently. ``values`` is a scalar/sequence of ints
    (strings hash to ints); no-op on a single process.
    """
    _chaos_collective(f"assert_same:{name}")
    if jax.process_count() <= 1:
        return
    import numpy as np
    from jax.experimental import multihost_utils

    def canon(v):
        if isinstance(v, str):
            import zlib

            return zlib.crc32(v.encode())
        return int(v)

    if isinstance(values, (list, tuple)):
        local = np.asarray([canon(v) for v in values], np.int64)
    else:
        local = np.asarray([canon(values)], np.int64)
    # only the allgather runs under the deadline: the divergence check
    # below must raise its own RuntimeError, never a retried one
    gathered = np.asarray(get_retry_policy().run(
        f"assert_same:{name}",
        lambda: multihost_utils.process_allgather(local)))
    if not (gathered == gathered[0]).all():
        rows = {i: gathered[i].tolist() for i in range(gathered.shape[0])}
        raise RuntimeError(
            f"cross-process consistency check failed for {name!r}: "
            f"processes disagree — per-process values {rows}. All hosts "
            "must run identical configs/checkpoints (reference "
            "assert_ints_same_as_other_ranks, runtime/zero/utils.py:106)")


def any_process(value: bool) -> bool:
    """True when ANY process reports ``value`` truthy (collective; every
    process must call it — the companion to assert_same_across_processes
    for per-rank conditions like missing per-rank files, where one rank
    raising alone would leave its peers hung in the next collective)."""
    if jax.process_count() <= 1:
        return bool(value)
    import numpy as np
    from jax.experimental import multihost_utils

    gathered = np.asarray(get_retry_policy().run(
        "any_process",
        lambda: multihost_utils.process_allgather(
            np.asarray([int(bool(value))], np.int64))))
    return bool(gathered.any())


# -- capability probes (reference comm/comm.py:325,629) ---------------------


def has_all_gather_into_tensor() -> bool:
    return True


def has_reduce_scatter_tensor() -> bool:
    return True


def has_coalescing_manager() -> bool:
    return True  # XLA coalesces/fuses collectives during scheduling


# -- in-jit named-axis collectives ------------------------------------------
# These are usable inside shard_map/pjit bodies. `axis` is a mesh axis name
# or tuple of names. Each records traced bytes with the CommsLogger.


def _nbytes(x) -> int:
    aval = jax.core.get_aval(x) if not hasattr(x, "nbytes") else x
    try:
        return int(aval.nbytes)
    except Exception:
        import numpy as np

        return int(np.prod(aval.shape) * jnp.dtype(aval.dtype).itemsize)


class _traced_op:
    """Dispatch→completion span around one traced collective: records
    the comms logger at entry (byte accounting, unchanged) and appends
    ONE flight-recorder event stamped with the dispatch start plus a
    ``dur_ms`` field at exit — so chrome_trace.py renders each traced
    collective as a Perfetto "X" slice on the comm lane instead of an
    instant marker, and overlapping dispatches show as overlapping
    slices. These fire at trace time (timing executed collectives inside
    a compiled program from Python is meaningless); the span covers the
    primitive's trace-time dispatch, which is also what a hang dump
    needs: which collectives the wedged program contains, in order."""

    __slots__ = ("_op", "_nb", "_axis", "_t0")

    def __init__(self, op: str, x, axis, log_name=None):
        name = log_name or op
        self._op = name
        self._axis = str(axis)
        self._nb = None
        try:
            self._nb = _nbytes(x)
            get_comms_logger().record(op, self._nb, axis, log_name)
        except Exception:
            pass

    def __enter__(self):
        import time as _time

        self._t0 = _time.time()
        return self

    def __exit__(self, *exc):
        import time as _time

        try:
            from deepspeed_tpu.observability.flight_recorder import \
                get_flight_recorder

            rec = get_flight_recorder()
            if rec.enabled:
                rec._ring.append((self._t0, "collective", {
                    "op": self._op, "bytes": self._nb, "axis": self._axis,
                    "dur_ms": (_time.time() - self._t0) * 1e3}))
        except Exception:
            pass
        return False


def all_reduce(x, axis, op: str = "sum", log_name: Optional[str] = None):
    """lax.psum/pmean/pmax over a named mesh axis (reference all_reduce
    comm/comm.py:497)."""
    with _traced_op("all_reduce", x, axis, log_name):
        if op == "sum":
            return lax.psum(x, axis)
        if op in ("avg", "mean"):
            return lax.pmean(x, axis)
        if op == "max":
            return lax.pmax(x, axis)
        if op == "min":
            return lax.pmin(x, axis)
    raise ValueError(f"unsupported reduce op: {op}")


def all_gather(x, axis, *, tiled: bool = True, gather_dim: int = 0,
               log_name: Optional[str] = None):
    """all_gather_into_tensor analog (comm/comm.py:320)."""
    with _traced_op("all_gather", x, axis, log_name):
        return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def reduce_scatter(x, axis, *, scatter_dim: int = 0, op: str = "sum",
                   log_name: Optional[str] = None):
    """reduce_scatter_tensor analog (comm/comm.py:257)."""
    with _traced_op("reduce_scatter", x, axis, log_name):
        out = lax.psum_scatter(x, axis, scatter_dimension=scatter_dim,
                               tiled=True)
        if op in ("avg", "mean"):
            out = out / jax.lax.axis_size(axis)
        return out


def all_to_all(x, axis, *, split_dim: int, concat_dim: int,
               log_name: Optional[str] = None):
    """all_to_all_single analog (comm/comm.py:392); the Ulysses primitive."""
    with _traced_op("all_to_all", x, axis, log_name):
        return lax.all_to_all(x, axis, split_axis=split_dim,
                              concat_axis=concat_dim, tiled=True)


def ppermute(x, axis, perm, log_name: Optional[str] = None):
    """Point-to-point ring shift (the reference's p2p send/recv
    pipe/p2p.py:46,67 becomes a collective-permute on TPU)."""
    with _traced_op("ppermute", x, axis, log_name):
        return lax.ppermute(x, axis, perm)


def traced_span(op: str, x, axis, log_name: Optional[str] = None):
    """Context manager giving GSPMD-implicit collectives the same byte
    accounting + flight-recorder span the explicit wrappers above get.

    Some collectives are not dispatched as lax primitives but emitted by
    the partitioner from sharding constraints (Ulysses's all-to-alls in
    parallel/ulysses.py). Wrap the constraint in ``traced_span`` so the
    collective still lands in the comms logger and on the chrome-trace
    collective lane::

        with comm.traced_span("all_to_all", q, "sp", "ulysses_qkv"):
            q = _constrain(q, head_sharded_spec)
    """
    return _traced_op(op, x, axis, log_name)


def broadcast(x, axis, root: int = 0, log_name: Optional[str] = None):
    """Broadcast from `root` along a named axis (comm/comm.py:227)."""
    with _traced_op("broadcast", x, axis, log_name):
        idx = lax.axis_index(axis)
        masked = jnp.where(idx == root, x, jnp.zeros_like(x))
        return lax.psum(masked, axis)


def axis_index(axis):
    return lax.axis_index(axis)


def axis_size(axis):
    return jax.lax.axis_size(axis)


def configure(config=None) -> None:
    """Wire the comms logger (reference dist.configure engine.py:323)
    and install the control-plane RetryPolicy from the ``resilience``
    config block."""
    global _POLICY
    if config is not None:
        get_comms_logger().configure(config.comms_logger)
        rcfg = getattr(config, "resilience", None)
        if rcfg is not None and getattr(rcfg, "enabled", True):
            from deepspeed_tpu.resilience.policy import RetryPolicy

            _POLICY = RetryPolicy.from_config(rcfg)


def log_summary(show_straggler: bool = False) -> str:
    return get_comms_logger().log_summary()
