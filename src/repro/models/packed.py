"""Sequence-packed (ragged) fragment execution.

Instead of padding every payload in a batch to a common length and
stacking along a batch axis, heterogeneous-length payloads are
concatenated along the TOKEN axis into one ``(1, T)`` buffer with
cu_seqlens-style segment boundaries. Per-token segment ids mask
attention so packed requests never attend across each other, and
per-segment positions restart RoPE at every boundary — making the
packed forward numerically identical to running each request alone.

Only the tail of the buffer is padded (to a quantized token bucket,
``serving.batcher.token_bucket``), so padding waste is bounded by the
bucket rounding regardless of how the batch mixes lengths — where
pad-to-bucket stacking pays ``max_len - len_i`` per request.

Compile-cache collapse: the packed program is keyed by fragment DEPTH
(``end - start``) plus the static embed/head boundary flags, with the
start offset a *traced* scalar sliced out of the stacked block params
via ``lax.dynamic_slice_in_dim``. Pools at different offsets but equal
depth share ONE compiled program, so a replan that shifts block ranges
re-uses the compile instead of churning the cache.

Packability: families whose per-token math is invariant to how tokens
are grouped into batches. ``dense`` always qualifies; ``moe`` only with
the dense dispatch (the grouped-GEMM path sizes its expert capacity
from the TOTAL token count, so packing would change routing/dropping);
recurrent families (``ssm``/``hybrid``) scan over time and would leak
state across segment boundaries; ``vlm``/``audio`` carry per-request
extras (image/frame memory) that have no packed layout. Non-packable
pools fall back to the pad-to-bucket path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig
from repro.models.transformer import (n_fragment_units, stack_forward,
                                      unembed)

Array = jax.Array


def is_packable(cfg: ModelConfig, extras=None) -> bool:
    """Can this (config, extras) combination run sequence-packed?"""
    if extras:
        return False
    if cfg.family == "dense":
        return True
    if cfg.family == "moe":
        return cfg.moe_impl == "dense"
    return False


def pack_segments(lengths, pad_to: int):
    """Packed layout for ``lengths`` padded to ``pad_to`` total tokens.

    Returns ``(seg_ids, positions, cu_seqlens)``: ``seg_ids`` (pad_to,)
    int32 gives each token its request index (pad tokens get the
    out-of-range id ``len(lengths)`` so they form their own segment);
    ``positions`` (pad_to,) int32 restarts at 0 per segment (RoPE);
    ``cu_seqlens`` (len+1,) are the segment boundary offsets —
    request ``i`` owns tokens ``[cu[i], cu[i+1])``.
    """
    lengths = [int(n) for n in lengths]
    total = sum(lengths)
    if pad_to < total:
        raise ValueError(f"pad_to={pad_to} < total tokens {total}")
    cu = np.zeros(len(lengths) + 1, np.int64)
    np.cumsum(lengths, out=cu[1:])
    seg = np.empty(pad_to, np.int32)
    pos = np.empty(pad_to, np.int32)
    for i, n in enumerate(lengths):
        seg[cu[i]:cu[i + 1]] = i
        pos[cu[i]:cu[i + 1]] = np.arange(n, dtype=np.int32)
    seg[total:] = len(lengths)
    pos[total:] = np.arange(pad_to - total, dtype=np.int32)
    return seg, pos, cu


def _packed_forward(params, inputs, seg_ids, positions, start, *,
                    cfg: ModelConfig, depth: int, embed: bool, head: bool):
    """Blocks ``[start, start+depth)`` over a packed ``(1, T)`` buffer.

    ``start`` is a traced scalar: the block slice comes out of the
    stacked layer params with ``dynamic_slice_in_dim``, so the compiled
    program depends only on (depth, embed, head) — not on where in the
    stack the fragment sits.
    """
    x = inputs
    if embed:
        x = params["embed"][inputs]
    blocks = jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, start, depth, axis=0),
        params["blocks"])
    x, _ = stack_forward(blocks, cfg, x, window=cfg.sliding_window,
                         seg_ids=seg_ids, positions=positions)
    if head:
        x = unembed(params, cfg, x)
    return x


# One compiled program per (model shape, depth, boundary flags) — shared
# across every FragmentInstance in the process, which is the whole point:
# replans that move block ranges hit this cache instead of recompiling.
_PACKED_FNS: dict = {}


def _cfg_key(cfg: ModelConfig) -> tuple:
    return (cfg.name, cfg.family, cfg.n_layers, cfg.d_model, cfg.n_heads,
            cfg.n_kv_heads, cfg.head_dim_, cfg.vocab_size,
            cfg.sliding_window, cfg.dtype, cfg.moe_impl, cfg.qk_norm,
            cfg.attn_bias, cfg.rope_theta, cfg.tie_embeddings)


def packed_fragment_fn(cfg: ModelConfig, depth: int, embed: bool,
                       head: bool):
    """The cached jitted packed program for any fragment of ``depth``
    blocks. Call as ``fn(params, inputs, seg_ids, positions, start)``
    with ``inputs`` (1, T) int32 token ids when ``embed`` else
    (1, T, d) hidden states. The kernel implementation in force is part
    of the key: a trace under one never serves a call under another."""
    from repro.kernels.ops import get_default_impl
    key = _cfg_key(cfg) + (int(depth), bool(embed), bool(head),
                           get_default_impl())
    fn = _PACKED_FNS.get(key)
    if fn is None:
        fn = _PACKED_FNS[key] = jax.jit(functools.partial(
            _packed_forward, cfg=cfg, depth=int(depth),
            embed=bool(embed), head=bool(head)))
    return fn


def run_fragment_packed(params, cfg: ModelConfig, payloads, start: int,
                        end: int, *, pad_to=None) -> list:
    """Run blocks ``[start, end)`` over per-request ``payloads`` packed
    into one buffer; returns the per-request outputs (pad stripped).

    ``payloads``: token ids (S_i,) when start == 0, else hidden states
    (S_i, d). ``pad_to`` pads the packed token axis (e.g. to a
    power-of-two bucket); default is the exact total.
    """
    L = n_fragment_units(cfg)
    lengths = [int(np.shape(p)[0]) for p in payloads]
    total = sum(lengths)
    T = int(pad_to) if pad_to else total
    seg, pos, cu = pack_segments(lengths, T)
    cat = jnp.concatenate([jnp.asarray(p) for p in payloads], axis=0)
    if T > total:
        cat = jnp.pad(cat, ((0, T - total),) + ((0, 0),) * (cat.ndim - 1))
    fn = packed_fragment_fn(cfg, end - start, start == 0, end == L)
    y = fn(params, cat[None], jnp.asarray(seg)[None], jnp.asarray(pos)[None],
           np.int32(start))
    return [y[0, int(cu[i]):int(cu[i + 1])] for i in range(len(lengths))]
