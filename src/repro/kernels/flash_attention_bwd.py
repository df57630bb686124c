"""Flash attention BACKWARD Pallas TPU kernels + custom_vjp wiring.

FlashAttention-2-style backward: the forward saves per-row logsumexp (L);
backward recomputes the probability tiles blockwise, so no (Sq x Sk)
materialisation:

  D  = rowsum(dO * O)                                (precomputed, fp32)
  p  = exp(q k^T * scale - L)
  dv += p^T dO
  dp = dO v^T
  ds = p * (dp - D) * scale
  dk += ds^T q
  dq += ds k

Two kernels: dq iterates (B, H, q-block, kv-block) accumulating into a dq
scratch; dkv iterates (B, KV-head, kv-block, q-block) accumulating dk/dv
for all q heads of the GQA group (so dk/dv land directly in the kv-head
layout). Per-row L and D travel as (S, 1) columns, so their tiles span
a whole unit dim and are legal at any block size. ``kv_len`` masks keys
at or past it: the ops layer pads a length without a legal tile and
differentiates through the pad. ``flash_attention_trainable`` is the
custom_vjp entry the ops layer uses on the pallas paths: its primal is
the forward-only kernel (``kernels.flash_attention``), so only
differentiated calls run the logsumexp-saving forward here.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import flash_attention

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward with logsumexp output (same math as flash_attention)
# ---------------------------------------------------------------------------

def _mask(qi, ki, *, causal, window, kv_len, bq, bk):
    """The (bq, bk) tile's visible (query, key) pairs."""
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    if kv_len is not None:
        mask &= kpos < kv_len
    return mask


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                scale, causal, window, kv_len, bq, bk):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_mask(qi, ki, causal=causal, window=window, kv_len=kv_len,
                        bq=bq, bk=bk), s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _fin():
        l = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_scr[...] + jnp.log(l)


def _flash_fwd(q, k, v, *, causal, window, kv_len, scale, bq, bk, interpret):
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    qt, kt, vt = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          window=window, kv_len=kv_len, bq=bq, bk=bk),
        grid=(B, H, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt)
    return o.transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _p_ds(q, k, v, do, lse, dvec, *, scale, causal, window, kv_len, bq, bk,
          qi, ki):
    # lse, dvec: (bq, 1) columns
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = _mask(qi, ki, causal=causal, window=window, kv_len=kv_len,
                 bq=bq, bk=bk)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - dvec) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref, dq_ref, acc_scr,
               *, scale, causal, window, kv_len, bq, bk):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    _, ds = _p_ds(q, k, v, do, lse_ref[0, 0], d_ref[0, 0], scale=scale,
                  causal=causal, window=window, kv_len=kv_len, bq=bq, bk=bk,
                  qi=qi, ki=ki)
    acc_scr[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _fin():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, window, kv_len, bq, bk, group):
    # grid: (B, KV, kv-block, q-block * group) — inner dim sweeps q blocks
    # for every q head in the GQA group so dk/dv accumulate per kv head.
    ji = pl.program_id(2)
    inner = pl.program_id(3)
    qi = inner // group

    @pl.when(inner == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    p, ds = _p_ds(q, k, v, do, lse_ref[0, 0], d_ref[0, 0], scale=scale,
                  causal=causal, window=window, kv_len=kv_len, bq=bq, bk=bk,
                  qi=qi, ki=ji)
    dv_scr[...] += jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    dk_scr[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(inner == pl.num_programs(3) - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(res, g, *, causal, window, kv_len, scale, bq, bk, interpret):
    q, k, v, o, lse = res
    do = g
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    group = H // KV
    dvec = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True).transpose(0, 2, 1, 3)  # (B,H,Sq,1)
    qt, dot_, ot = (a.transpose(0, 2, 1, 3) for a in (q, do, o))
    kt, vt = (a.transpose(0, 2, 1, 3) for a in (k, v))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, kv_len=kv_len, bq=bq, bk=bk),
        grid=(B, H, Sq // bq, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, dvec)

    nq = Sq // bq
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, kv_len=kv_len, bq=bq, bk=bk,
                          group=group),
        grid=(B, KV, Sk // bk, nq * group),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, h, j, i: (b, h * group + i % group,
                                             i // group, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bq, hd),
                         lambda b, h, j, i: (b, h * group + i % group,
                                             i // group, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b, h, j, i: (b, h * group + i % group,
                                             i // group, 0)),
            pl.BlockSpec((1, 1, bq, 1),
                         lambda b, h, j, i: (b, h * group + i % group,
                                             i // group, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, j, i: (b, h, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, KV, Sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, KV, Sk, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot_, lse, dvec)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


# ---------------------------------------------------------------------------
# custom_vjp entry
# ---------------------------------------------------------------------------

def _key_limit(kv_len, Sk):
    """``kv_len`` as the kernels take it: None when no key is masked."""
    return kv_len if kv_len is not None and kv_len < Sk else None


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention_trainable(q, k, v, causal=True, window=0,
                              scale=None, block_q=128, block_k=128,
                              interpret=False, kv_len=None):
    # undifferentiated calls (serving) run the forward-only kernel; the
    # logsumexp-saving forward below runs only under jax.grad
    B, Sq, Sk = q.shape[0], q.shape[1], k.shape[1]
    seg = kv_seg = None
    if _key_limit(kv_len, Sk) is not None:
        # keys at or past kv_len carry segment id -1 and are never seen
        seg = jnp.zeros((B, Sq), jnp.int32)
        kv_seg = jnp.broadcast_to(
            jnp.where(jnp.arange(Sk) < kv_len, 0, -1).astype(jnp.int32),
            (B, Sk))
    return flash_attention(q, k, v, seg, kv_seg, causal=causal,
                           window=window, scale=scale, block_q=block_q,
                           block_k=block_k, interpret=interpret)


def _vjp_fwd(q, k, v, causal, window, scale, block_q, block_k, interpret,
             kv_len):
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    o, lse = _flash_fwd(q, k, v, causal=causal, window=window,
                        kv_len=_key_limit(kv_len, k.shape[1]), scale=scale,
                        bq=min(block_q, q.shape[1]),
                        bk=min(block_k, k.shape[1]), interpret=interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, window, scale, block_q, block_k, interpret, kv_len,
             res, g):
    q, k = res[0], res[1]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash_bwd(res, g, causal=causal, window=window,
                      kv_len=_key_limit(kv_len, k.shape[1]), scale=scale,
                      bq=min(block_q, q.shape[1]),
                      bk=min(block_k, k.shape[1]), interpret=interpret)


flash_attention_trainable.defvjp(_vjp_fwd, _vjp_bwd)
