"""Quantization kernel + quantized collective tests
(reference analogs: tests/unit/ops/quantizer, tests/unit/runtime/zero/test_zeropp.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.pallas.quantization import (
    dequantize_blockwise, pack_int4, quantize_blockwise, quantized_all_gather,
    quantized_psum_scatter, unpack_int4)
from deepspeed_tpu.parallel.topology import TopologyConfig, build_mesh


def test_int8_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 512), jnp.float32)
    q, s = quantize_blockwise(x, bits=8, block=256)
    assert q.dtype == jnp.int8 and s.shape == (64, 2)
    y = dequantize_blockwise(q, s, bits=8, block=256, dtype=jnp.float32)
    err = np.abs(np.asarray(y) - np.asarray(x)).max()
    scale_max = np.asarray(s).max()
    assert err <= scale_max * 0.51 + 1e-6  # half-ULP of the quant grid


def test_int4_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 256), jnp.float32)
    q, s = quantize_blockwise(x, bits=4, block=128)
    assert int(np.asarray(q).max()) <= 7 and int(np.asarray(q).min()) >= -8
    y = dequantize_blockwise(q, s, bits=4, block=128, dtype=jnp.float32)
    err = np.abs(np.asarray(y) - np.asarray(x)).max()
    assert err <= np.asarray(s).max() * 0.51 + 1e-6


def test_int4_pack_unpack_roundtrip():
    q = jnp.asarray(np.random.default_rng(0).integers(-8, 8, (4, 64)),
                    jnp.int8)
    np.testing.assert_array_equal(np.asarray(unpack_int4(pack_int4(q))),
                                  np.asarray(q))


def test_zero_block_is_stable():
    x = jnp.zeros((8, 256))
    q, s = quantize_blockwise(x)
    y = dequantize_blockwise(q, s, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y), 0.0)


def test_quantized_all_gather_close_to_exact(devices):
    mesh = build_mesh(TopologyConfig(dp=1, fsdp=8))
    x = jax.random.normal(jax.random.PRNGKey(2), (16, 256), jnp.float32)

    out = shard_map(
        lambda v: quantized_all_gather(v, "fsdp", bits=8, block=256),
        mesh=mesh, in_specs=P("fsdp", None), out_specs=P(None, None),
        check_vma=False)(x)
    assert out.shape == x.shape
    err = np.abs(np.asarray(out) - np.asarray(x)).max()
    assert err < 0.05, err  # int8 grid error on unit-normal data


def test_quantized_psum_scatter_close_to_exact(devices):
    mesh = build_mesh(TopologyConfig(dp=1, fsdp=8))
    # replicate input: every rank contributes the same grad block
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 256), jnp.float32)

    exact = shard_map(
        lambda v: jax.lax.psum_scatter(v, "fsdp", scatter_dimension=0,
                                       tiled=True) / 8.0,
        mesh=mesh, in_specs=P(None, None), out_specs=P("fsdp", None),
        check_vma=False)(x)
    quant = shard_map(
        lambda v: quantized_psum_scatter(v, "fsdp", bits=8, block=256),
        mesh=mesh, in_specs=P(None, None), out_specs=P("fsdp", None),
        check_vma=False)(x)
    err = np.abs(np.asarray(quant) - np.asarray(exact)).max()
    assert err < 0.05, err


def test_wire_bytes_shrink():
    """The point of ZeRO++: int8 halves, int4 quarters the wire volume."""
    x = jnp.zeros((1024, 1024), jnp.bfloat16)
    q8, s8 = quantize_blockwise(x, bits=8)
    assert q8.size * 1 < x.size * 2  # int8 vs bf16
    q4, _ = quantize_blockwise(x, bits=4)
    packed = pack_int4(q4)
    assert packed.size * 1 <= x.size  # nibbles vs bf16 = 4x cut
