"""Plain float32 forward of OLMo-1B (arXiv:2402.00838), from its
configuration file alone: the yardstick each served answer is compared
with.

Per block: LayerNorm with no parameters (subtract the mean, divide by
sqrt(variance + eps), no scale and no bias) -> q, k, v projections (no
bias, no q/k norm) -> rotary embedding -> causal softmax attention with
one key/value head per query head -> output projection -> residual;
LayerNorm -> SwiGLU MLP -> residual. Then a final LayerNorm and the tied
embedding as the output head. ``eps`` is the file's ``layer_norm_eps``.
The rotary embedding and the matmuls (with the fp8 control) are
``dense.py``'s, so both references rotate and round alike.

``param_shapes`` is the weight layout this reference reads, which the
harness makes from the seed and hands to the program as well: the norms
have no parameters, so the tree holds none. ``program_attrs`` is what the
program's own config must say to run the same model.

``mode="fp8"`` is the control: every matmul of the projections, the MLP
and the output head takes its weight and its input rounded to float8
e4m3. Everything runs at ``Precision.HIGHEST``, one layer at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.references.dense import HI, _mm, _rope


def param_shapes(spec: dict) -> dict:
    """{path: (shape, dtype)} of a dense gated-MLP transformer with tied
    embeddings, full multi-head attention and parameter-free norms."""
    L, d = spec["num_hidden_layers"], spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, f, V = spec["head_dim"], spec["intermediate_size"], spec["vocab_size"]
    wt = spec["torch_dtype"]
    return {
        ("embed",): ((V, d), wt),
        ("blocks", "attn", "wq"): ((L, d, H * hd), wt),
        ("blocks", "attn", "wk"): ((L, d, KV * hd), wt),
        ("blocks", "attn", "wv"): ((L, d, KV * hd), wt),
        ("blocks", "attn", "wo"): ((L, H * hd, d), wt),
        ("blocks", "mlp", "w_gate"): ((L, d, f), wt),
        ("blocks", "mlp", "w_up"): ((L, d, f), wt),
        ("blocks", "mlp", "w_down"): ((L, f, d), wt),
    }


def program_attrs(spec: dict) -> dict:
    """Attributes of the program's model config that must hold for it to
    compute this reference's model."""
    return {"family": "dense", "gated_mlp": True, "rmsnorm": False,
            "nonparametric_ln": True, "sliding_window": 0,
            "dtype": spec["torch_dtype"]}


def _norm(x, spec):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + spec["layer_norm_eps"])


def _block(h, lw, spec, mode):
    S = h.shape[0]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    a = f32["attn"]
    x = _norm(h, spec)
    q = _mm(x, a["wq"], mode).reshape(S, H, hd)
    k = _mm(x, a["wk"], mode).reshape(S, KV, hd)
    v = _mm(x, a["wv"], mode).reshape(S, KV, hd)
    pos = jnp.arange(S)
    q, k = _rope(q, pos, spec["rope_theta"]), _rope(k, pos, spec["rope_theta"])
    g = H // KV
    s = jnp.einsum("qkgd,tkd->kgqt", q.reshape(S, KV, g, hd), k,
                   precision=HI) * hd ** -0.5
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI).reshape(S, H * hd)
    h = h + _mm(o, a["wo"], mode)
    m = f32["mlp"]
    x = _norm(h, spec)
    y = jax.nn.silu(_mm(x, m["w_gate"], mode)) * _mm(x, m["w_up"], mode)
    return h + _mm(y, m["w_down"], mode)


def logits(weights, spec: dict, tokens, mode: str = "ref"):
    """float32 logits (S, V) of one sequence ``tokens`` (S,)."""
    h = weights["embed"][tokens].astype(jnp.float32)
    blocks = {k: weights["blocks"][k] for k in ("attn", "mlp")}
    h, _ = jax.lax.scan(lambda c, lw: (_block(c, lw, spec, mode), None),
                        h, blocks)
    h = _norm(h, spec)
    return _mm(h, weights["embed"].astype(jnp.float32).T, mode)
