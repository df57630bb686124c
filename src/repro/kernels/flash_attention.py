"""Flash attention Pallas TPU kernel (prefill path).

TPU-native adaptation: blocked online-softmax over a (batch, q-head, q-block,
kv-block) grid; q/k/v tiles staged HBM->VMEM via BlockSpec, fp32 running
(m, l, acc) scratch in VMEM, MXU-aligned tiles (multiples of 128 on the
contracting dims). GQA is handled in the BlockSpec index maps (a q head reads
its kv head directly — kv is never materialised repeated in HBM).

Supports causal masking and optional sliding-window masking; non-causal mode
serves encoder/cross attention.
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

DEFAULT_BQ = 256
DEFAULT_BK = 256


def _attn_kernel(*refs, scale: float, causal: bool, window: int,
                 bq: int, bk: int, n_kv: int, has_seg: bool = False):
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref,
         o_ref, m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
        sq_ref = sk_ref = None
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk

    # Skip fully-masked blocks (beyond the causal frontier / outside window).
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window:
        run = jnp.logical_and(run, q_start - (k_start + bk - 1) < window) \
            if causal else run

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= (qpos - kpos) < window
        if has_seg:
            mask &= sq_ref[0] == sk_ref[0]             # (bq, 1) == (1, bk)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _finalize():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)                    # fully-masked rows -> 0
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "block_q", "block_k", "interpret"))
def flash_attention(q: Array, k: Array, v: Array,
                    segment_ids: Optional[Array] = None,
                    kv_segment_ids: Optional[Array] = None, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BQ, block_k: int = DEFAULT_BK,
                    interpret: bool = False) -> Array:
    """q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd) -> (B, Sq, H, hd).

    Positions are implicit (q token i is global position i) — the prefill case.

    segment_ids (B, Sq) int32: sequence-packed batches — scores are masked
    to segment equality so packed requests never attend across each other.
    Pad tokens carry their own id. ``kv_segment_ids`` (B, Sk) defaults to
    ``segment_ids`` (self-attention); passing both lets a caller mask
    padded keys when Sq != Sk.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    assert H % KV == 0
    scale = scale if scale is not None else hd ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)

    # layout: (B, H, S, hd) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, H, Sq // bq, Sk // bk)
    group = H // KV

    has_seg = segment_ids is not None
    if kv_segment_ids is None:
        kv_segment_ids = segment_ids
    in_specs = [
        pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
        pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
    ]
    operands = [qt, kt, vt]
    if has_seg:
        # q ids as a (bq, 1) column, kv ids as a (1, bk) row: both tiles
        # span a whole unit dim, so they are legal at any B and block size
        in_specs += [pl.BlockSpec((1, bq, 1), lambda b, h, i, j: (b, i, 0)),
                     pl.BlockSpec((1, 1, bk), lambda b, h, i, j: (b, 0, j))]
        operands += [segment_ids.astype(jnp.int32).reshape(B, Sq, 1),
                     kv_segment_ids.astype(jnp.int32).reshape(B, 1, Sk)]

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=scale, causal=causal,
                          window=window, bq=bq, bk=bk, n_kv=KV,
                          has_seg=has_seg),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return out.transpose(0, 2, 1, 3)
