"""Prefill + single-token decode with stacked (over layers) caches.

``serve_step`` (the dry-run entry for decode_32k / long_500k) is
:func:`decode_step`: ONE new token against a cache of ``cache_len`` slots.
Windowed archs use a ring-buffer cache of ``min(seq, window)`` slots; the
ssm/hybrid families carry O(1) recurrent state instead of / alongside KV.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models import attention as attn
from repro.models import layers as nn
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models import ssm as ssm_mod
from repro.models.transformer import embed_tokens, unembed, encode_audio

Array = jax.Array


# hybrid models carry O(1) recurrent state (ssm_scan) for long-range
# context, so their attention branch only ever needs a bounded local
# window — but configs that leave sliding_window unset used to fall
# through to the full-seq_len KV branch and allocate an unbounded cache.
HYBRID_DEFAULT_WINDOW = 1024


def decode_window(cfg: ModelConfig) -> int:
    """Effective attention window for decode caches, sized from FAMILY,
    not just the sliding_window knob: ssm (rwkv) carries no KV at all;
    hybrid defaults to a bounded local window because its scan state
    covers the long range. 0 means unwindowed (full causal KV)."""
    if cfg.family == "ssm":
        return 0
    if cfg.family == "hybrid":
        return cfg.sliding_window or HYBRID_DEFAULT_WINDOW
    return cfg.sliding_window or 0


def cache_len_for(cfg: ModelConfig, seq_len: int) -> int:
    if cfg.family == "ssm":
        return 0
    W = decode_window(cfg)
    if W:
        return min(seq_len, W)
    return seq_len


def init_cache(cfg: ModelConfig, batch: int, seq_len: int) -> dict:
    """Zeroed cache pytree sized for ``seq_len`` context."""
    dt = jnp.dtype(cfg.dtype)
    L, KV, hd, d = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim_, cfg.d_model
    c: dict = {"pos": jnp.zeros((batch,), jnp.int32)}
    Sc = cache_len_for(cfg, seq_len)
    if cfg.family == "ssm":
        H, rhd = rwkv_mod.rwkv_dims(cfg)
        c["wkv"] = jnp.zeros((L, batch, H, rhd, rhd), jnp.float32)
        c["shift_tm"] = jnp.zeros((L, batch, 1, d), dt)
        c["shift_cm"] = jnp.zeros((L, batch, 1, d), dt)
        return c
    c["kv_pos"] = jnp.full((batch, Sc), -1, jnp.int32)
    kv_dt = jnp.dtype(cfg.kv_cache_dtype or cfg.dtype)
    quant = kv_dt == jnp.int8
    if cfg.family == "vlm":
        G = cfg.n_layers // cfg.vision.cross_attn_every
        E = cfg.vision.cross_attn_every
        c["k"] = jnp.zeros((G, E, batch, Sc, KV, hd), kv_dt)
        c["v"] = jnp.zeros((G, E, batch, Sc, KV, hd), kv_dt)
        if quant:
            c["k_scale"] = jnp.zeros((G, E, batch, Sc, KV), jnp.float32)
            c["v_scale"] = jnp.zeros((G, E, batch, Sc, KV), jnp.float32)
        c["img_k"] = jnp.zeros((G, batch, cfg.vision.n_image_tokens, KV, hd), dt)
        c["img_v"] = jnp.zeros((G, batch, cfg.vision.n_image_tokens, KV, hd), dt)
        return c
    c["k"] = jnp.zeros((L, batch, Sc, KV, hd), kv_dt)
    c["v"] = jnp.zeros((L, batch, Sc, KV, hd), kv_dt)
    if quant:
        c["k_scale"] = jnp.zeros((L, batch, Sc, KV), jnp.float32)
        c["v_scale"] = jnp.zeros((L, batch, Sc, KV), jnp.float32)
    if cfg.family == "audio":
        F = cfg.audio.n_audio_frames
        c["xk"] = jnp.zeros((L, batch, F, KV, hd), dt)
        c["xv"] = jnp.zeros((L, batch, F, KV, hd), dt)
    if cfg.family == "hybrid":
        d_in, H, shd = ssm_mod.ssm_dims(cfg)
        c["ssm_conv"] = jnp.zeros((L, batch, cfg.ssm.conv_width - 1, d_in), dt)
        c["ssm_scan"] = jnp.zeros((L, batch, H, shd, cfg.ssm.state_dim),
                                  jnp.float32)
    return c


# ---------------------------------------------------------------------------
# Decode blocks
# ---------------------------------------------------------------------------

def _block_decode(p: dict, cfg: ModelConfig, x: Array, c: dict,
                  pos: Array, kv_pos: Array, *, kind: str = "self",
                  memory_kv=None) -> tuple[Array, dict]:
    """One-token decode through one block. c holds this layer's cache slice."""
    new_c = dict(c)
    if cfg.family == "ssm":
        h = nn.apply_norm(nn.norm_params(p, "ln1"), cfg, x)
        y, new_c["shift_tm"], new_c["wkv"] = rwkv_mod.time_mix_decode(
            p["time_mix"], cfg, h, c["shift_tm"], c["wkv"])
        x = x + y
        h = nn.apply_norm(nn.norm_params(p, "ln2"), cfg, x)
        y, new_c["shift_cm"] = rwkv_mod.channel_mix(
            p["channel_mix"], cfg, h, shift_carry=c["shift_cm"])
        return x + y, new_c
    if kind == "cross":
        h = nn.apply_norm(nn.norm_params(p, "ln1"), cfg, x)
        y, _, _, _ = attn.attn_decode(p["xattn"], cfg, h, None, None, pos,
                                      kv_pos, cross_kv=memory_kv)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * y
        h = nn.apply_norm(nn.norm_params(p, "ln2"), cfg, x)
        return x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) \
            * nn.apply_mlp(p["mlp"], cfg, h), new_c
    h = nn.apply_norm(nn.norm_params(p, "ln1"), cfg, x)
    scales = (c["k_scale"], c["v_scale"]) if "k_scale" in c else None
    y, new_c["k"], new_c["v"], new_scales = attn.attn_decode(
        p["attn"], cfg, h, c["k"], c["v"], pos, kv_pos,
        window=decode_window(cfg), scales=scales)
    if new_scales is not None:
        new_c["k_scale"], new_c["v_scale"] = new_scales
    if cfg.family == "hybrid":
        ys, new_c["ssm_conv"], new_c["ssm_scan"] = ssm_mod.ssm_decode(
            p["ssm"], cfg, h, c["ssm_conv"], c["ssm_scan"])
        y = 0.5 * (y + ys)
    x = x + y
    if kind == "dec":
        h = nn.apply_norm(nn.norm_params(p, "lnx"), cfg, x)
        y, _, _, _ = attn.attn_decode(p["xattn"], cfg, h, None, None, pos,
                                      kv_pos, cross_kv=memory_kv)
        x = x + y
    h = nn.apply_norm(nn.norm_params(p, "ln2"), cfg, x)
    if cfg.family == "moe":
        y, _ = moe_mod.moe_forward(p["moe"], cfg, h)
    else:
        y = nn.apply_mlp(p["mlp"], cfg, h)
    return x + y, new_c


def _layer_cache_keys(cfg: ModelConfig) -> tuple[str, ...]:
    if cfg.family == "ssm":
        return ("wkv", "shift_tm", "shift_cm")
    keys = ("k", "v")
    if cfg.kv_cache_dtype == "int8":
        keys += ("k_scale", "v_scale")
    if cfg.family == "hybrid":
        keys += ("ssm_conv", "ssm_scan")
    if cfg.family == "audio":
        keys += ("xk", "xv")
    return keys


def decode_step(params: dict, cfg: ModelConfig, cache: dict, tokens: Array
                ) -> tuple[Array, dict]:
    """ONE token step. tokens (B,1) -> (logits (B,1,V), new cache)."""
    B = tokens.shape[0]
    pos = cache["pos"]
    x = embed_tokens(params, cfg, tokens)
    if cfg.family == "audio":
        pe = nn.sinusoid_pos_emb(4096, cfg.d_model)
        x = x + pe[jnp.clip(pos, 0, 4095)][:, None].astype(x.dtype)

    kv_pos = cache.get("kv_pos")
    if kv_pos is not None and kv_pos.shape[1] > 0:
        kv_pos = attn.update_kv_pos(kv_pos, pos, kv_pos.shape[1],
                                    decode_window(cfg))

    lkeys = _layer_cache_keys(cfg)

    if cfg.family == "vlm":
        def gbody(h, xs):
            p_g, c_g, img_kv = xs
            def sbody(hh, ys):
                p_l, c_l = ys
                hh, c_new = _block_decode(p_l, cfg, hh, c_l, pos, kv_pos)
                return hh, c_new
            keys = ("k", "v") + (("k_scale", "v_scale")
                                 if cfg.kv_cache_dtype == "int8" else ())
            h, c_new = jax.lax.scan(sbody, h,
                                    (p_g["self"], {k: c_g[k] for k in keys}))
            h, _ = _block_decode(p_g["cross"], cfg, h, {}, pos, kv_pos,
                                 kind="cross", memory_kv=img_kv)
            return h, c_new
        stacked_p = {"self": params["blocks"], "cross": params["cross_blocks"]}
        ckeys = ("k", "v") + (("k_scale", "v_scale")
                              if cfg.kv_cache_dtype == "int8" else ())
        stacked_c = {k: cache[k] for k in ckeys}
        img_kv = (cache["img_k"], cache["img_v"])
        x, new_layer_c = jax.lax.scan(gbody, x, (stacked_p, stacked_c, img_kv))
        new_cache = dict(cache)
        new_cache.update(new_layer_c)
    else:
        kind = "dec" if cfg.family == "audio" else "self"

        def body(h, xs):
            p_l, c_l = xs
            mem_kv = (c_l.pop("xk"), c_l.pop("xv")) if cfg.family == "audio" \
                else None
            h, c_new = _block_decode(p_l, cfg, h, c_l, pos, kv_pos,
                                     kind=kind, memory_kv=mem_kv)
            if mem_kv is not None:
                c_new["xk"], c_new["xv"] = mem_kv
            return h, c_new

        layer_c = {k: cache[k] for k in lkeys}
        x, new_layer_c = jax.lax.scan(body, x, (params["blocks"], layer_c))
        new_cache = dict(cache)
        new_cache.update(new_layer_c)

    if kv_pos is not None:
        new_cache["kv_pos"] = kv_pos
    new_cache["pos"] = pos + 1
    logits = unembed(params, cfg, x)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def _to_ring(full: Array, S: int, W: int) -> Array:
    """(…,S,…) position-major kv -> (…,W,…) ring layout (slot = pos % W)."""
    last = jax.lax.dynamic_slice_in_dim(full, S - W, W, axis=2)
    slots = (jnp.arange(S - W, S)) % W
    out = jnp.zeros_like(last)
    return out.at[:, :, slots].set(last)


def prefill(params: dict, cfg: ModelConfig, tokens: Array, *,
            extras: Optional[dict] = None, cache_seq: Optional[int] = None
            ) -> tuple[Array, dict]:
    """Full-sequence forward that also fills a decode cache.

    Returns (logits (B,S,V), cache ready for decode at pos=S).
    """
    from repro.models.transformer import block_forward
    extras = extras or {}
    B, S = tokens.shape
    cache_seq = cache_seq or S
    cache = init_cache(cfg, B, cache_seq)
    Sc = cache_len_for(cfg, cache_seq)
    x = embed_tokens(params, cfg, tokens)

    if cfg.family == "ssm":
        def body(h, p_l):
            hn = nn.apply_norm(nn.norm_params(p_l, "ln1"), cfg, h)
            y, sh_tm, wkv = rwkv_mod.time_mix_forward(p_l["time_mix"], cfg, hn)
            h = h + y
            hn = nn.apply_norm(nn.norm_params(p_l, "ln2"), cfg, h)
            y, sh_cm = rwkv_mod.channel_mix(p_l["channel_mix"], cfg, hn)
            return h + y, {"wkv": wkv, "shift_tm": sh_tm, "shift_cm": sh_cm}
        x, lc = jax.lax.scan(body, x, params["blocks"])
        cache.update(lc)
        cache["pos"] = jnp.full((B,), S, jnp.int32)
        return unembed(params, cfg, x), cache

    W = decode_window(cfg)
    quant = (cfg.kv_cache_dtype or cfg.dtype) == "int8"

    def capture(k, v):
        if quant:
            k, ks_ = attn.quantize_kv(k)
            v, vs_ = attn.quantize_kv(v)
        kv = jnp.stack([k, v])                              # (2,B,S,KV,hd)
        if W and Sc < S:
            kv = _to_ring(kv, S, Sc)
        elif Sc > S:                                        # pad to capacity
            kv = jnp.pad(kv, ((0, 0), (0, 0), (0, Sc - S), (0, 0), (0, 0)))
        if quant:
            sc = jnp.stack([ks_, vs_])                      # (2,B,S,KV)
            if W and Sc < S:
                sc = _to_ring(sc, S, Sc)
            elif Sc > S:
                sc = jnp.pad(sc, ((0, 0), (0, 0), (0, Sc - S), (0, 0)))
            return kv, sc
        return kv

    if cfg.family == "vlm":
        img = extras["images"]
        def gbody(h, p_g):
            def sbody(hh, p_l):
                hn = nn.apply_norm(nn.norm_params(p_l, "ln1"), cfg, hh)
                y, (k, v) = attn.attn_forward(p_l["attn"], cfg, hn,
                                              window=W, return_kv=True)
                hh = hh + y
                hn = nn.apply_norm(nn.norm_params(p_l, "ln2"), cfg, hh)
                hh = hh + nn.apply_mlp(p_l["mlp"], cfg, hn)
                cap = capture(k, v)
                return hh, (cap if not quant else {"kv": cap[0],
                                                   "sc": cap[1]})
            h, kvs = jax.lax.scan(sbody, h, p_g["self"])
            h, _ = block_forward(p_g["cross"], cfg, h, memory=img,
                                 kind="cross")
            ik, iv = attn.project_cross_kv(p_g["cross"]["xattn"], cfg, img)
            return h, (kvs, jnp.stack([ik, iv]))
        stacked_p = {"self": params["blocks"], "cross": params["cross_blocks"]}
        x, (kvs, img_kvs) = jax.lax.scan(gbody, x, stacked_p)
        if quant:
            cache["k"], cache["v"] = kvs["kv"][:, :, 0], kvs["kv"][:, :, 1]
            cache["k_scale"] = kvs["sc"][:, :, 0]
            cache["v_scale"] = kvs["sc"][:, :, 1]
        else:
            cache["k"], cache["v"] = kvs[:, :, 0], kvs[:, :, 1]
        cache["img_k"], cache["img_v"] = img_kvs[:, 0], img_kvs[:, 1]
    else:
        mem = None
        kind = "self"
        if cfg.family == "audio":
            x = x + nn.sinusoid_pos_emb(S, cfg.d_model).astype(x.dtype)[None]
            mem = encode_audio(params, cfg, extras["frames"])
            kind = "dec"

        def body(h, p_l):
            hn = nn.apply_norm(nn.norm_params(p_l, "ln1"), cfg, h)
            y, (k, v) = attn.attn_forward(p_l["attn"], cfg, hn, window=W,
                                          return_kv=True)
            lc = {}
            if cfg.family == "hybrid":
                ys, lc["ssm_conv"], lc["ssm_scan"] = \
                    ssm_mod.ssm_forward_with_state(p_l["ssm"], cfg, hn)
                y = 0.5 * (y + ys)
            h = h + y
            if kind == "dec":
                hn = nn.apply_norm(nn.norm_params(p_l, "lnx"), cfg, h)
                h = h + attn.attn_forward(p_l["xattn"], cfg, hn, kv_src=mem,
                                          causal=False)
                xk, xv = attn.project_cross_kv(p_l["xattn"], cfg, mem)
                lc["xk"], lc["xv"] = xk, xv
            hn = nn.apply_norm(nn.norm_params(p_l, "ln2"), cfg, h)
            if cfg.family == "moe":
                y, _ = moe_mod.moe_forward(p_l["moe"], cfg, hn)
            else:
                y = nn.apply_mlp(p_l["mlp"], cfg, hn)
            cap = capture(k, v)
            lc["kv"] = cap[0] if quant else cap
            if quant:
                lc["kv_sc"] = cap[1]
            from repro.distributed.actspec import constrain
            return constrain(h + y), lc
        x, lc = jax.lax.scan(body, x, params["blocks"])
        kvs = lc.pop("kv")                                  # (L,2,B,Sc,KV,hd)
        cache["k"], cache["v"] = kvs[:, 0], kvs[:, 1]
        if quant:
            scs = lc.pop("kv_sc")
            cache["k_scale"], cache["v_scale"] = scs[:, 0], scs[:, 1]
        cache.update(lc)

    # kv_pos: which global position occupies each cache slot
    if Sc >= S:                                            # plain cache
        kvp = jnp.where(jnp.arange(Sc) < S, jnp.arange(Sc), -1)
    else:                                                  # ring buffer
        pos_range = jnp.arange(S - Sc, S)
        kvp = jnp.zeros((Sc,), jnp.int32).at[pos_range % Sc].set(pos_range)
    cache["kv_pos"] = jnp.broadcast_to(kvp[None], (B, Sc)).astype(jnp.int32)
    cache["pos"] = jnp.full((B,), S, jnp.int32)
    return unembed(params, cfg, x), cache
