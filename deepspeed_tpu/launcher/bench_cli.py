"""Aux benchmark CLIs.

Reference: ``bin/ds_bench`` (communication benchmark sweep, backed by
DeepSpeedExamples' comm suite) and ``bin/ds_io`` / ``bin/ds_nvme_tune``
(DeepNVMe async-I/O throughput sweep, deepspeed/nvme/).

  * ``dstpu-bench``: collective bandwidth sweep (all_reduce /
    all_gather / reduce_scatter / all_to_all) over a mesh axis, sizes
    swept in powers of two; reports algorithmic bus bandwidth the same
    way the reference's comm benchmarks do.
  * ``dstpu-io``: file read/write throughput through the native AIO
    handle (block size × queue-depth sweep — the ds_nvme_tune
    parameter space).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List


# ---------------------------------------------------------------------------
# dstpu-bench: collective sweep
# ---------------------------------------------------------------------------

def _bus_bandwidth(op: str, nbytes: int, world: int, dt: float) -> float:
    """Algorithmic bus bandwidth in GB/s (reference comms convention:
    ring all-reduce moves 2(n-1)/n of the data, gather/scatter (n-1)/n)."""
    if world <= 1:
        return nbytes / dt / 1e9
    if op == "all_reduce":
        factor = 2 * (world - 1) / world
    elif op in ("all_gather", "reduce_scatter", "all_to_all"):
        factor = (world - 1) / world
    else:
        factor = 1.0
    return nbytes * factor / dt / 1e9


def bench_collectives(axis: str = "dp", sizes_mb: List[float] = (1, 4, 16, 64),
                      ops: List[str] = ("all_reduce", "all_gather",
                                        "reduce_scatter", "all_to_all"),
                      iters: int = 10, out=print) -> List[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu import comm
    from deepspeed_tpu.parallel import topology as topo

    sizes = {axis: -1}
    if axis != "dp":
        sizes["dp"] = 1  # TopologyConfig defaults dp=-1; only one free axis
    mesh = topo._GLOBAL_MESH or topo.build_mesh(topo.TopologyConfig(**sizes))
    world = mesh.shape[axis]
    results = []
    for op in ops:
        for mb in sizes_mb:
            n = int(mb * 1e6 / 4)
            # per-shard count (n/world) must itself divide by world for the
            # all_to_all reshape; round to a world*world multiple
            unit = world * world
            n = max(unit, (n // unit) * unit)
            x = jnp.ones((n,), jnp.float32)

            def body(x):
                if op == "all_reduce":
                    return comm.all_reduce(x, axis)
                if op == "all_gather":
                    return comm.all_gather(x, axis)
                if op == "reduce_scatter":
                    return comm.reduce_scatter(x, axis)
                return comm.all_to_all(x.reshape(world, -1), axis,
                                       split_dim=0, concat_dim=1)

            fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(axis),
                                       out_specs=P(axis), check_vma=False))
            r = fn(x)
            jax.block_until_ready(r)  # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                r = fn(x)
            jax.block_until_ready(r)
            dt = (time.perf_counter() - t0) / iters
            nbytes = x.size * 4 // world  # per-shard payload
            # collective buffer size S per the nccl-tests convention the
            # reference benchmarks follow: all_reduce/reduce_scatter/
            # all_to_all use the per-rank buffer, all_gather the aggregate
            S = nbytes * world if op == "all_gather" else nbytes
            bw = _bus_bandwidth(op, S, world, dt)
            rec = {"op": op, "axis": axis, "world": world,
                   "size_mb": round(S / 1e6, 2),
                   "time_ms": round(dt * 1e3, 3),
                   "busbw_gbps": round(bw, 2)}
            results.append(rec)
            out(json.dumps(rec))
    return results


def bench_main(argv=None):
    ap = argparse.ArgumentParser(
        prog="dstpu-bench",
        description="collective bandwidth sweep (reference bin/ds_bench)")
    ap.add_argument("--axis", default="dp")
    ap.add_argument("--sizes-mb", type=float, nargs="+",
                    default=[1, 4, 16, 64])
    ap.add_argument("--ops", nargs="+",
                    default=["all_reduce", "all_gather", "reduce_scatter",
                             "all_to_all"])
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    bench_collectives(args.axis, args.sizes_mb, args.ops, args.iters)
    return 0


# ---------------------------------------------------------------------------
# dstpu-io: AIO throughput sweep
# ---------------------------------------------------------------------------

def bench_io(path: str, size_mb: int = 256, block_sizes=(1, 8, 16),
             queue_depths=(4, 16, 32), read: bool = True,
             write: bool = True, backends=("threads", "auto"),
             out=print) -> List[dict]:
    import numpy as np

    from deepspeed_tpu.ops.native.aio import (AsyncIOHandle,
                                              DEFAULT_BLOCK_SIZE)

    if not read and not write:
        raise ValueError("nothing to do: enable read and/or write")
    if read and not write and not os.path.exists(path):
        raise FileNotFoundError(
            f"read-only sweep needs an existing file at {path}")
    if write and os.path.exists(path):
        raise FileExistsError(
            f"refusing to overwrite existing file {path} — the write sweep "
            "clobbers and deletes its scratch file; pass a fresh path")
    os.makedirs(os.path.dirname(os.path.abspath(path)) or ".", exist_ok=True)
    if read and not write:
        # size from the user's file; never delete it
        data = np.empty(os.path.getsize(path), dtype=np.uint8)
        size_mb = data.nbytes // (1024 * 1024)
    else:
        data = np.random.default_rng(0).integers(
            0, 255, size_mb * 1024 * 1024, dtype=np.uint8)
    results = []
    for backend in backends:
      for bs_mult in block_sizes:
        for qd in queue_depths:
            # pin every knob: a stale tuned config must not parameterize
            # the benchmark that tuned configs are derived from
            from deepspeed_tpu.ops.native.aio import DEFAULT_THREADS

            handle = AsyncIOHandle(block_size=bs_mult * DEFAULT_BLOCK_SIZE,
                                   queue_depth=qd,
                                   num_threads=DEFAULT_THREADS,
                                   backend=backend)
            if write:
                t0 = time.perf_counter()
                handle.pwrite(data, path)
                dt = time.perf_counter() - t0
                rec = {"op": "write", "size_mb": size_mb,
                       "backend": handle.backend,
                       "block_kb": bs_mult * DEFAULT_BLOCK_SIZE // 1024,
                       "queue_depth": qd,
                       "gbps": round(data.nbytes / dt / 1e9, 3)}
                results.append(rec)
                out(json.dumps(rec))
            if read:
                buf = np.empty_like(data)
                t0 = time.perf_counter()
                handle.pread(buf, path)
                dt = time.perf_counter() - t0
                rec = {"op": "read", "size_mb": size_mb,
                       "backend": handle.backend,
                       "block_kb": bs_mult * DEFAULT_BLOCK_SIZE // 1024,
                       "queue_depth": qd,
                       "gbps": round(data.nbytes / dt / 1e9, 3)}
                results.append(rec)
                out(json.dumps(rec))
            handle.close()
    if write:  # only delete scratch files this sweep created
        try:
            os.unlink(path)
        except OSError:
            pass
    return results


def io_main(argv=None):
    ap = argparse.ArgumentParser(
        prog="dstpu-io",
        description="async file I/O throughput sweep (reference bin/ds_io "
                    "+ ds_nvme_tune)")
    ap.add_argument("path", help="scratch file on the device to test")
    ap.add_argument("--size-mb", type=int, default=256)
    ap.add_argument("--block-mults", type=int, nargs="+", default=[1, 8, 16])
    ap.add_argument("--queue-depths", type=int, nargs="+",
                    default=[4, 16, 32])
    ap.add_argument("--read-only", action="store_true")
    ap.add_argument("--write-only", action="store_true")
    args = ap.parse_args(argv)
    if args.read_only and args.write_only:
        ap.error("--read-only and --write-only are mutually exclusive")
    bench_io(args.path, args.size_mb, args.block_mults, args.queue_depths,
             read=not args.write_only, write=not args.read_only)
    return 0


if __name__ == "__main__":
    sys.exit(bench_main())
