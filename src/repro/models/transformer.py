"""Generic block-stacked model covering all assigned families.

Layer stacking uses ``jax.lax.scan`` over parameter pytrees with a leading
layer axis, so the lowered HLO is O(1) in depth (critical for the 64/100
layer archs in the dry-run). Re-alignment (the paper's technique) cuts the
stack at block granularity: :func:`fragment_forward` executes blocks
``[start, end)`` on externally supplied hidden states — this is the exact
substrate operation Graft's alignment/shared stages run.

Families:
  dense   — [ln -> GQA attn] + [ln -> (swiglu|gelu) mlp]
  moe     — attn + MoE mlp (grouped-GEMM dispatch)
  hybrid  — parallel attn + mamba2-style SSM heads (hymba), then mlp
  ssm     — RWKV6 time-mix + channel-mix (attention-free)
  vlm     — dense blocks with a gated cross-attn block every N layers
            (llama-3.2-vision); image embeddings come from the stub frontend
  audio   — whisper-style enc-dec; frame embeddings come from the stub
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod

Array = jax.Array
PyTree = Any


def _maybe_remat(body, remat):
    """remat: False | True/'full' (recompute everything) | 'dots' (save
    matmul outputs — trades per-layer activation memory for ~25% less
    backward recompute; §Perf train iteration)."""
    if not remat:
        return body
    if remat == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


# ---------------------------------------------------------------------------
# Block init
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, *, kind: str = "self") -> dict:
    """kind: self | cross (vlm gated cross block) | enc (bidirectional) |
    dec (whisper decoder: self + cross)."""
    ks = jax.random.split(key, 6)
    p: dict = nn.init_norm(cfg, "ln1", "ln2")
    if cfg.family == "ssm":
        p["time_mix"] = rwkv_mod.init_time_mix(ks[0], cfg)
        p["channel_mix"] = rwkv_mod.init_channel_mix(ks[1], cfg)
        return p
    if kind == "cross":
        p["xattn"] = attn.init_attention(ks[0], cfg, cross=True)
        p["mlp"] = nn.init_mlp(ks[1], cfg)
        p["gate_attn"] = jnp.zeros((), jnp.float32)
        p["gate_mlp"] = jnp.zeros((), jnp.float32)
        return p
    p["attn"] = attn.init_attention(ks[0], cfg)
    if kind == "dec":
        p["xattn"] = attn.init_attention(ks[1], cfg, cross=True)
        p.update(nn.init_norm(cfg, "lnx"))
    if cfg.family == "moe":
        p["moe"] = moe_mod.init_moe(ks[2], cfg)
    else:
        p["mlp"] = nn.init_mlp(ks[2], cfg)
    if cfg.family == "hybrid":
        p["ssm"] = ssm_mod.init_ssm(ks[3], cfg)
    return p


def init_stack(key, cfg: ModelConfig, n_layers: int, *, kind: str = "self"):
    keys = jax.random.split(key, n_layers)
    return jax.vmap(lambda k: init_block(k, cfg, kind=kind))(keys)


def init_params(key, cfg: ModelConfig) -> dict:
    ks = jax.random.split(key, 6)
    dt = jnp.dtype(cfg.dtype)
    p: dict = {
        "embed": nn.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dt),
        **nn.init_norm(cfg, "final_norm"),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = nn.dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)
    if cfg.family == "vlm":
        vz = cfg.vision
        G = cfg.n_layers // vz.cross_attn_every
        E = vz.cross_attn_every
        keys = jax.random.split(ks[2], G)
        p["blocks"] = jax.vmap(
            lambda k: init_stack(k, cfg, E, kind="self"))(keys)
        p["cross_blocks"] = init_stack(ks[3], cfg, G, kind="cross")
    elif cfg.family == "audio":
        p["enc_blocks"] = init_stack(ks[2], cfg, cfg.audio.n_encoder_layers,
                                     kind="enc")
        p.update(nn.init_norm(cfg, "enc_norm"))
        p["blocks"] = init_stack(ks[3], cfg, cfg.n_layers, kind="dec")
    else:
        p["blocks"] = init_stack(ks[2], cfg, cfg.n_layers, kind="self")
    return p


# ---------------------------------------------------------------------------
# Full-sequence block application (train / prefill / fragments)
# ---------------------------------------------------------------------------

def block_forward(p: dict, cfg: ModelConfig, x: Array, *,
                  window: int = 0, causal: bool = True,
                  memory: Optional[Array] = None,
                  kind: str = "self",
                  seg_ids: Optional[Array] = None,
                  positions: Optional[Array] = None) -> tuple[Array, Array]:
    """One block, full sequence. Returns (x, moe_aux).

    seg_ids/positions (B, S) carry the sequence-packed layout
    (``models.packed``): attention is masked to segment boundaries and
    RoPE restarts per segment. None = the ordinary unpacked batch.
    """
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "ssm":
        y, _, _ = rwkv_mod.time_mix_forward(
            p["time_mix"], cfg,
            nn.apply_norm(nn.norm_params(p, "ln1"), cfg, x))
        x = x + y
        y, _ = rwkv_mod.channel_mix(
            p["channel_mix"], cfg,
            nn.apply_norm(nn.norm_params(p, "ln2"), cfg, x))
        return x + y, aux
    if kind == "cross":
        h = nn.apply_norm(nn.norm_params(p, "ln1"), cfg, x)
        y = attn.attn_forward(p["xattn"], cfg, h, kv_src=memory, causal=False)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * y
        h = nn.apply_norm(nn.norm_params(p, "ln2"), cfg, x)
        x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) \
            * nn.apply_mlp(p["mlp"], cfg, h)
        return x, aux
    h = nn.apply_norm(nn.norm_params(p, "ln1"), cfg, x)
    y = attn.attn_forward(p["attn"], cfg, h, window=window, causal=causal,
                          positions=positions, seg_ids=seg_ids)
    if cfg.family == "hybrid":
        y = 0.5 * (y + ssm_mod.ssm_forward(p["ssm"], cfg, h))
    x = x + y
    if kind == "dec":
        h = nn.apply_norm(nn.norm_params(p, "lnx"), cfg, x)
        x = x + attn.attn_forward(p["xattn"], cfg, h, kv_src=memory,
                                  causal=False)
    h = nn.apply_norm(nn.norm_params(p, "ln2"), cfg, x)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_forward(p["moe"], cfg, h)
    else:
        y = nn.apply_mlp(p["mlp"], cfg, h)
    return x + y, aux


def stack_forward(blocks: PyTree, cfg: ModelConfig, x: Array, *,
                  window: int = 0, causal: bool = True,
                  memory: Optional[Array] = None, kind: str = "self",
                  remat: bool = False,
                  seg_ids: Optional[Array] = None,
                  positions: Optional[Array] = None) -> tuple[Array, Array]:
    """scan blocks over the leading layer axis. Returns (x, total_moe_aux)."""
    from repro.distributed.actspec import constrain

    def body(carry, p_l):
        h, aux = carry
        h, a = block_forward(p_l, cfg, h, window=window, causal=causal,
                             memory=memory, kind=kind,
                             seg_ids=seg_ids, positions=positions)
        return (constrain(h), aux + a), None

    fn = _maybe_remat(body, remat)
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)), blocks)
    return x, aux


def vlm_stack_forward(params: dict, cfg: ModelConfig, x: Array,
                      img: Array, *, window: int = 0,
                      remat: bool = False) -> tuple[Array, Array]:
    """Scan over superblocks: E self layers then one gated cross block."""
    from repro.distributed.actspec import constrain

    def body(carry, p_g):
        h, aux = carry
        h, a = stack_forward(p_g["self"], cfg, h, window=window)
        h, _ = block_forward(p_g["cross"], cfg, h, memory=img, kind="cross")
        return (constrain(h), aux + a), None

    fn = _maybe_remat(body, remat)
    stacked = {"self": params["blocks"], "cross": params["cross_blocks"]}
    (x, aux), _ = jax.lax.scan(fn, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


# ---------------------------------------------------------------------------
# Model facade
# ---------------------------------------------------------------------------

def embed_tokens(params: dict, cfg: ModelConfig, tokens: Array) -> Array:
    return params["embed"][tokens]


def unembed(params: dict, cfg: ModelConfig, x: Array) -> Array:
    x = nn.apply_norm(nn.norm_params(params, "final_norm"), cfg, x)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head


def encode_audio(params: dict, cfg: ModelConfig, frames: Array) -> Array:
    """Whisper encoder over stub frame embeddings (B, F, d)."""
    F = frames.shape[1]
    pos = nn.sinusoid_pos_emb(F, cfg.d_model).astype(frames.dtype)
    h = frames + pos[None]
    h, _ = stack_forward(params["enc_blocks"], cfg, h, causal=False,
                         kind="enc")
    return nn.apply_norm(nn.norm_params(params, "enc_norm"), cfg, h)


def forward(params: dict, cfg: ModelConfig, tokens: Array, *,
            extras: Optional[dict] = None, remat: bool = False
            ) -> tuple[Array, Array]:
    """Full forward (training / logits-only prefill).

    extras: {"images": (B,Timg,d)} for vlm; {"frames": (B,F,d)} for audio.
    Returns (logits, moe_aux).
    """
    extras = extras or {}
    x = embed_tokens(params, cfg, tokens)
    if cfg.family == "audio":
        x = x + nn.sinusoid_pos_emb(tokens.shape[1],
                                    cfg.d_model).astype(x.dtype)[None]
        mem = encode_audio(params, cfg, extras["frames"])
        x, aux = stack_forward(params["blocks"], cfg, x, memory=mem,
                               kind="dec", remat=remat)
    elif cfg.family == "vlm":
        x, aux = vlm_stack_forward(params, cfg, x, extras["images"],
                                   window=cfg.sliding_window, remat=remat)
    else:
        x, aux = stack_forward(params["blocks"], cfg, x,
                               window=cfg.sliding_window, remat=remat)
    return unembed(params, cfg, x), aux


# ---------------------------------------------------------------------------
# Fragment execution (the substrate operation for DNN re-alignment)
# ---------------------------------------------------------------------------

def n_fragment_units(cfg: ModelConfig) -> int:
    """Number of re-partitionable units ("layers" in Graft's sense)."""
    if cfg.family == "vlm":
        return cfg.n_layers // cfg.vision.cross_attn_every
    return cfg.n_layers


def fragment_forward(params: dict, cfg: ModelConfig, hidden: Array,
                     start: int, end: int, *,
                     extras: Optional[dict] = None) -> Array:
    """Run blocks [start, end) on hidden states — Graft stage execution."""
    extras = extras or {}
    sl = lambda t: jax.tree.map(lambda a: a[start:end], t)
    if cfg.family == "vlm":
        img = extras["images"]
        x, _ = vlm_stack_forward(
            {"blocks": sl(params["blocks"]),
             "cross_blocks": sl(params["cross_blocks"])},
            cfg, hidden, img, window=cfg.sliding_window)
        return x
    if cfg.family == "audio":
        mem = extras["memory"]
        x, _ = stack_forward(sl(params["blocks"]), cfg, hidden,
                             memory=mem, kind="dec")
        return x
    x, _ = stack_forward(sl(params["blocks"]), cfg, hidden,
                         window=cfg.sliding_window)
    return x


def run_fragment(params: dict, cfg: ModelConfig, inputs: Array,
                 start: int, end: int, *,
                 extras: Optional[dict] = None) -> Array:
    """Fragment execution including the embed (start==0) and head (end==L)
    boundary work — what a serving instance actually runs."""
    L = n_fragment_units(cfg)
    x = inputs
    if start == 0:
        x = embed_tokens(params, cfg, inputs)
        if cfg.family == "audio":
            x = x + nn.sinusoid_pos_emb(x.shape[1],
                                        cfg.d_model).astype(x.dtype)[None]
    x = fragment_forward(params, cfg, x, start, end, extras=extras)
    if end == L:
        x = unembed(params, cfg, x)
    return x
