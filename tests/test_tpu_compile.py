"""Compile the served Pallas kernels for a described TPU v5e at qwen3-1.7b
widths (16 query heads, 8 KV heads, head_dim 128, bf16).

Nothing here runs: the TPU compiler refuses what the chip would refuse
(illegal tiles, too much VMEM), which interpret-mode tests cannot show.
The topology is described inside a fixture — never while a module is
imported — so every xdist worker collects the same tests and only the
worker given this file loads the TPU library. Keep all such compiles in
this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

H, KV, HD = 16, 8, 128
DT = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    """A described-topology compile is written to the persistent cache but
    cannot be read back without a chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _spec(shape, sharding, dtype=DT):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_decode_attention_batched(one_chip):
    """Continuous batching steps the decode kernel at the pool's batch."""
    B, ctx = 4, 256

    def step(q, k, v, q_pos, kv_pos):
        return ops.attend_cache(q, k, v, q_pos, kv_pos, impl="pallas")

    _assert_kernel(step, _spec((B, 1, H, HD), one_chip),
                   _spec((B, ctx, KV, HD), one_chip),
                   _spec((B, ctx, KV, HD), one_chip),
                   _spec((B,), one_chip, jnp.int32),
                   _spec((B, ctx), one_chip, jnp.int32))


@pytest.mark.parametrize("T", [48, 272, 512])
def test_packed_flash_attention(one_chip, T):
    """Packed totals are multiples of 16; 272 has no 128-aligned divisor
    tile and must be padded."""
    def packed(q, k, v, seg):
        return ops.attention(q, k, v, causal=True, seg_ids=seg,
                             impl="pallas")

    _assert_kernel(packed, _spec((1, T, H, HD), one_chip),
                   _spec((1, T, KV, HD), one_chip),
                   _spec((1, T, KV, HD), one_chip),
                   _spec((1, T), one_chip, jnp.int32))


@pytest.mark.parametrize("S", [12, 300])
def test_prefill_attention(one_chip, S):
    """The unpacked prefill of a decode admission and of the mobile part."""
    def prefill(q, k, v):
        return ops.attention(q, k, v, causal=True, impl="pallas")

    _assert_kernel(prefill, _spec((1, S, H, HD), one_chip),
                   _spec((1, S, KV, HD), one_chip),
                   _spec((1, S, KV, HD), one_chip))


@pytest.mark.parametrize("S", [12, 300])
def test_attention_gradient(one_chip, S):
    """Training differentiates the same entry point: the logsumexp-saving
    forward and both backward kernels compile, padded length included."""
    def loss(q, k, v):
        out = ops.attention(q, k, v, causal=True, impl="pallas")
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        _spec((1, S, H, HD), one_chip), _spec((1, S, KV, HD), one_chip),
        _spec((1, S, KV, HD), one_chip)).compile().as_text()
    assert text.count("tpu_custom_call") >= 3     # forward, dq, dk/dv
