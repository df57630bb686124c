"""Shared layer primitives: norms, RoPE, MLPs, embeddings.

All modules are pure functions over parameter pytrees (nested dicts of
jnp arrays). Initialisers mirror the source model families (truncated-normal
fan-in scaling).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig

Array = jax.Array


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Initialisers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int, dtype) -> Array:
    std = 1.0 / math.sqrt(in_dim)
    return (jax.random.truncated_normal(key, -3, 3, (in_dim, out_dim), jnp.float32)
            * std).astype(dtype)


def embed_init(key, vocab: int, dim: int, dtype) -> Array:
    return (jax.random.truncated_normal(key, -3, 3, (vocab, dim), jnp.float32)
            * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Normalisation
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, *names: str) -> dict:
    """``{name: parameters}`` for each named norm. A non-parametric
    LayerNorm (OLMo) has no parameters and holds no node in the tree, so
    it yields no entry; readers take a norm through :func:`norm_params`."""
    if cfg.nonparametric_ln:
        return {}
    p = {"scale": jnp.ones((cfg.d_model,), jnp.float32)}
    if not cfg.rmsnorm:
        p["bias"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return {n: dict(p) for n in names}


def norm_params(p: dict, name: str) -> dict:
    """The parameters of norm ``name`` in ``p``; ``{}`` for a norm that
    has none."""
    return p.get(name, {})


def apply_norm(params: dict, cfg: ModelConfig, x: Array, eps: float = 1e-5) -> Array:
    """RMSNorm / LayerNorm / non-parametric LayerNorm (OLMo), fp32 internals."""
    xf = x.astype(jnp.float32)
    if cfg.rmsnorm:
        xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    if params:
        xf = xf * params["scale"]
        if "bias" in params:
            xf = xf + params["bias"]
    return xf.astype(x.dtype)


def rms_head_norm(scale: Array, x: Array, eps: float = 1e-6) -> Array:
    """Per-head RMSNorm used by qk_norm (qwen3 / olmoe)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * scale).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(cfg: ModelConfig, positions: Array) -> tuple[Array, Array]:
    """cos/sin tables for integer ``positions`` (any leading shape)."""
    hd = cfg.head_dim_
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[..., None] * inv          # (..., hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (..., n_heads, head_dim); cos/sin broadcastable to (..., hd/2).

    Interleaved-pair convention (x_even, x_odd rotation).
    """
    x1, x2 = x[..., 0::2], x[..., 1::2]
    cos = cos[..., None, :]                                       # add head axis
    sin = sin[..., None, :]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def sinusoid_pos_emb(length: int, dim: int) -> Array:
    """Whisper-style fixed sinusoidal embedding (length, dim)."""
    half = dim // 2
    inv = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32)
                  / max(half - 1, 1))
    ang = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> dict:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = _dtype(cfg)
    ks = jax.random.split(key, 3)
    if cfg.gated_mlp:
        p = {"w_gate": dense_init(ks[0], d, f, dt),
             "w_up": dense_init(ks[1], d, f, dt),
             "w_down": dense_init(ks[2], f, d, dt)}
    else:
        p = {"w_up": dense_init(ks[0], d, f, dt),
             "w_down": dense_init(ks[1], f, d, dt)}
    if cfg.mlp_bias:
        p["b_up"] = jnp.zeros((f,), dt)
        p["b_down"] = jnp.zeros((d,), dt)
    return p


def apply_mlp(params: dict, cfg: ModelConfig, x: Array) -> Array:
    if cfg.gated_mlp:
        g = jax.nn.silu(x @ params["w_gate"])
        u = x @ params["w_up"]
        h = g * u
    else:
        h = x @ params["w_up"]
        if "b_up" in params:
            h = h + params["b_up"]
        h = jax.nn.gelu(h)
    y = h @ params["w_down"]
    if "b_down" in params:
        y = y + params["b_down"]
    return y
