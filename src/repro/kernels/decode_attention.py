"""Flash-decode Pallas TPU kernel: one query token against a long KV cache.

TPU adaptation of GPU flash-decoding: the KV cache is streamed HBM->VMEM in
blocks along the sequence axis on a (batch, kv-head, kv-block) grid; the
online-softmax partials live in VMEM scratch. All q heads of one GQA group
are processed together (group dim is the sublane dim of the MXU tile), so a
grid step does a (group x bk) x (bk x hd) matmul rather than a vector op.

Ring-buffer caches are supported via an explicit kv_pos input: slots with
kv_pos == -1 (unwritten) or kv_pos > q_pos are masked.

Layouts follow the TPU tiling rule (the last two block dims are multiples
of (8, 128) or span the whole array dims): the per-row query positions
ride scalar prefetch into SMEM, and kv_pos is viewed as (B, 1, Sk) so its
(1, bk) tile is legal at any batch size.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30

DEFAULT_BK = 512


def _decode_kernel(qpos_ref, kvpos_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *,
                   scale: float, window: int, bk: int):
    ki = pl.program_id(2)
    qpos = qpos_ref[pl.program_id(0)]                      # scalar (SMEM)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                    # (group, hd)
    k = k_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                    # (bk, hd)
    kpos = kvpos_ref[0]                                    # (1, bk) int32

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    valid = (kpos >= 0) & (kpos <= qpos)
    if window:
        valid &= (qpos - kpos) < window
    s = jnp.where(valid, s, NEG_INF)                       # (group, bk)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "block_k", "interpret"))
def decode_attention(q: Array, k: Array, v: Array,
                     q_pos: Array, kv_pos: Array, *,
                     window: int = 0, scale: Optional[float] = None,
                     block_k: int = DEFAULT_BK,
                     interpret: bool = False) -> Array:
    """q: (B, 1, H, hd); k/v: (B, Sk, KV, hd); q_pos: (B,); kv_pos: (B, Sk).

    Returns (B, 1, H, hd).
    """
    B, Sq, H, hd = q.shape
    assert Sq == 1
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    scale = scale if scale is not None else hd ** -0.5
    bk = min(block_k, Sk)
    assert Sk % bk == 0

    qt = q.reshape(B, KV, group, hd)                       # group-major heads
    kt = k.transpose(0, 2, 1, 3)                           # (B, KV, Sk, hd)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, KV, Sk // bk)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bk), lambda b, h, j, qp: (b, 0, j)),
            pl.BlockSpec((1, 1, group, hd), lambda b, h, j, qp: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, qp: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, qp: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, hd),
                               lambda b, h, j, qp: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, window=window, bk=bk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, group, hd), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), kv_pos.astype(jnp.int32).reshape(B, 1, Sk),
      qt, kt, vt)
    return out.reshape(B, 1, H, hd)
