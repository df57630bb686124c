"""Dispatching wrappers over the Pallas kernels and their jnp references.

The models call these entry points. The implementation is chosen once per
process from the backend — ``pallas`` on TPU, ``reference`` elsewhere —
and ``use_impl`` / the ``impl=`` kwarg override it explicitly (tests):

  * ``reference``         — chunked pure-jnp (CPU execution, dry-run lowering)
  * ``pallas``            — compiled Pallas TPU kernel (the deployment target)
  * ``pallas_interpret``  — Pallas kernel body interpreted on CPU (tests)
  * ``naive``             — full-materialisation oracle (small tests only)

The choice is process-wide, not per thread: the server's pool-driver
threads trace the pool programs, and they must see the same kernels as
the thread that started them.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention_bwd import flash_attention_trainable
from repro.kernels.decode_attention import decode_attention
from repro.kernels.rwkv6_scan import wkv6_scan
from repro.kernels.ssm_scan import ssm_scan

Array = jax.Array

IMPLS = ("reference", "pallas", "pallas_interpret", "naive")

_override: Optional[str] = None


def backend_impl() -> str:
    """The implementation this process's backend runs: Pallas kernels on
    TPU, the jnp references everywhere else."""
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def get_default_impl() -> str:
    return _override or backend_impl()


@contextlib.contextmanager
def use_impl(impl: str):
    """Override the backend's choice, process-wide, inside the context."""
    global _override
    if impl not in IMPLS:
        raise ValueError(f"unknown kernel impl {impl!r}; known: {IMPLS}")
    prev, _override = _override, impl
    try:
        yield
    finally:
        _override = prev


def _resolve(impl: Optional[str]) -> str:
    return impl or get_default_impl()


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def attention(q: Array, k: Array, v: Array, *,
              causal: bool = True, window: int = 0,
              scale: Optional[float] = None,
              seg_ids: Optional[Array] = None,
              impl: Optional[str] = None) -> Array:
    """Prefill/training attention. q (B,Sq,H,hd), k/v (B,Sk,KV,hd).

    seg_ids (B, S) int32: sequence-packing segment mask for ragged
    batches (``models.packed``) — attention stays within segments.
    """
    impl = _resolve(impl)
    if impl == "naive":
        return _ref.ref_attention(q, k, v, causal=causal, window=window,
                                  seg_q=seg_ids, seg_kv=seg_ids,
                                  scale=scale)
    if impl == "reference":
        return _ref.chunked_attention(q, k, v, causal=causal, window=window,
                                      seg_ids=seg_ids, scale=scale)
    interp = impl == "pallas_interpret"
    Sq, Sk = q.shape[1], k.shape[1]
    bq, Sq_p = _seq_tile(Sq, 256)
    bk, Sk_p = _seq_tile(Sk, 256)
    q_p, k_p, v_p = _pad_seq(q, Sq_p), _pad_seq(k, Sk_p), _pad_seq(v, Sk_p)
    if seg_ids is None:
        # custom_vjp entry: undifferentiated it runs the forward-only
        # kernel; under jax.grad the Pallas backward kernels run. Keys
        # past Sk are masked, so the sliced result and its grads are exact
        out = flash_attention_trainable(q_p, k_p, v_p, causal, window, scale,
                                        bq, bk, interp, Sk)
        return out[:, :Sq]
    # packed (serving only, forward only): padded tails carry segment id
    # -1, so real queries never see a pad key
    out = flash_attention(
        q_p, k_p, v_p, _pad_seq(seg_ids, Sq_p, -1),
        _pad_seq(seg_ids, Sk_p, -1), causal=causal, window=window,
        scale=scale, block_q=bq, block_k=bk, interpret=interp)
    return out[:, :Sq]


def attend_cache(q: Array, k: Array, v: Array, q_pos: Array, kv_pos: Array, *,
                 window: int = 0, scale: Optional[float] = None,
                 impl: Optional[str] = None) -> Array:
    """Single-token decode attention against a (possibly ring-buffer) cache.

    q (B,1,H,hd), k/v (B,Sk,KV,hd), q_pos (B,), kv_pos (B,Sk).
    """
    impl = _resolve(impl)
    if impl in ("naive", "reference"):
        return _ref.ref_attention(q, k, v, q_pos=q_pos[:, None],
                                  kv_pos=kv_pos, causal=True, window=window,
                                  scale=scale)
    interp = impl == "pallas_interpret"
    bk, Sk_p = _seq_tile(k.shape[1], 512)
    return decode_attention(q, _pad_seq(k, Sk_p), _pad_seq(v, Sk_p), q_pos,
                            _pad_seq(kv_pos, Sk_p, -1), window=window,
                            scale=scale, block_k=bk, interpret=interp)


# ---------------------------------------------------------------------------
# RWKV6 WKV
# ---------------------------------------------------------------------------

def wkv6(r, k, v, w, u, state, *, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "naive":
        return _ref.ref_wkv6(r, k, v, w, u, state)
    if impl == "reference":
        return _ref.chunked_wkv6(r, k, v, w, u, state,
                                 chunk=_pick_block(r.shape[1], 32))
    interp = impl == "pallas_interpret"
    return wkv6_scan(r, k, v, w, u, state,
                     chunk=_pick_block(r.shape[1], 32), interpret=interp)


def wkv6_step(r, k, v, w, u, state):
    """One-token WKV6 update (decode path; recurrence is trivial here).

    r,k,v,w: (B,1,H,hd); state (B,H,hd,hd) fp32.
    """
    rt, kt, vt, wt = (x[:, 0].astype(jnp.float32) for x in (r, k, v, w))
    wt = jnp.exp(jnp.clip(jnp.log(jnp.clip(wt, 1e-12, 1.0)), -2.5, -1e-6))
    kv = kt[..., :, None] * vt[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", rt, state + u[None, :, :, None] * kv)
    new = wt[..., :, None] * state + kv
    return o[:, None].astype(r.dtype), new


# ---------------------------------------------------------------------------
# Selective SSM scan
# ---------------------------------------------------------------------------

def ssm(x, dt, A, Bm, Cm, state, *, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "naive":
        return _ref.ref_ssm_scan(x, dt, A, Bm, Cm, state)
    if impl == "reference":
        return _ref.chunked_ssm_scan(x, dt, A, Bm, Cm, state,
                                     chunk=_pick_block(x.shape[1], 32))
    interp = impl == "pallas_interpret"
    return ssm_scan(x, dt, A, Bm, Cm, state,
                    chunk=_pick_block(x.shape[1], 32), interpret=interp)


def ssm_step(x, dt, A, Bm, Cm, state):
    """One-token SSM update. x (B,1,H,hd); dt (B,1,H); Bm/Cm (B,1,N)."""
    xt = x[:, 0].astype(jnp.float32)
    dtt = dt[:, 0].astype(jnp.float32)
    bt, ct = Bm[:, 0].astype(jnp.float32), Cm[:, 0].astype(jnp.float32)
    a = jnp.exp(jnp.clip(dtt * A[None], -2.5, 0.0))
    h = a[..., None, None] * state + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
    y = jnp.einsum("bhdn,bn->bhd", h, ct)
    return y[:, None].astype(x.dtype), h


def _seq_tile(size: int, preferred: int) -> tuple[int, int]:
    """(tile, padded length) for a Pallas TPU sequence axis. The compiler
    accepts a tile that spans the whole axis or is a multiple of 128 that
    divides it, so an axis longer than ``preferred`` (itself a multiple of
    128) is padded to a multiple of 128 and tiled by the largest such
    divisor not above ``preferred``."""
    if size <= preferred:
        return size, size
    padded = -(-size // 128) * 128
    tile = preferred
    while padded % tile:
        tile -= 128
    return tile, padded


def _pad_seq(x: Array, length: int, value=0) -> Array:
    """Pad axis 1 of ``x`` up to ``length`` with ``value``."""
    if x.shape[1] == length:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, length - x.shape[1])
    return jnp.pad(x, pad, constant_values=value)


def _pick_block(size: int, preferred: int) -> int:
    b = min(preferred, size)
    while size % b:
        b -= 1
    return max(b, 1)
