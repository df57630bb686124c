"""Plain float32 forward of a dense transformer, from its configuration
file alone: the yardstick each served answer is compared with.

Per block: norm -> q, k, v projections -> per-head RMS norm of q and k
(when the configuration has qk-norm) -> rotary embedding -> causal
softmax attention with grouped key/value heads -> output projection ->
residual; norm -> SwiGLU MLP -> residual. Then the final norm and the
tied embedding as the output head. Norms are RMSNorm with a scale, in
float32, with the file's ``rms_norm_eps``. The rotary embedding
rotates interleaved channel pairs (2i, 2i+1) by position * theta^(-2i/hd),
as the program does; the published models pair channel i with i + hd/2,
which is the same function up to a fixed permutation of each head's q/k
channels, so with random weights neither is privileged.

``param_shapes`` is the weight layout this reference reads, which the
harness makes from the seed and hands to the program as well, and
``program_attrs`` what the program's own config must say to run the same
model (checked by the harness, which reads the program).

``mode="fp8"`` is the control: every matmul of the projections, the MLP
and the output head takes its weight and its input rounded to float8
(e4m3, one scale per output channel and per token), the lower precision a
bfloat16 server would be tempted to serve in. Everything runs at
``Precision.HIGHEST``, one layer at a time (weights are cast up inside
the layer scan), so the float32 copy of the model is never whole.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def param_shapes(spec: dict) -> dict:
    """{path: (shape, dtype)} of a dense gated-MLP transformer with tied
    embeddings, RMS norms and per-head q/k norms."""
    L, d = spec["num_hidden_layers"], spec["hidden_size"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd, f, V = spec["head_dim"], spec["intermediate_size"], spec["vocab_size"]
    wt = spec["torch_dtype"]
    out = {
        ("embed",): ((V, d), wt),
        ("blocks", "attn", "wq"): ((L, d, H * hd), wt),
        ("blocks", "attn", "wk"): ((L, d, KV * hd), wt),
        ("blocks", "attn", "wv"): ((L, d, KV * hd), wt),
        ("blocks", "attn", "wo"): ((L, H * hd, d), wt),
        ("blocks", "mlp", "w_gate"): ((L, d, f), wt),
        ("blocks", "mlp", "w_up"): ((L, d, f), wt),
        ("blocks", "mlp", "w_down"): ((L, f, d), wt),
        ("final_norm", "scale"): ((d,), "float32"),
        ("blocks", "ln1", "scale"): ((L, d), "float32"),
        ("blocks", "ln2", "scale"): ((L, d), "float32"),
    }
    if spec["qk_norm"]:
        out[("blocks", "attn", "q_norm")] = ((L, hd), "float32")
        out[("blocks", "attn", "k_norm")] = ((L, hd), "float32")
    return out


def program_attrs(spec: dict) -> dict:
    """Attributes of the program's model config that must hold for it to
    compute this reference's model."""
    return {"family": "dense", "gated_mlp": True, "rmsnorm": True,
            "nonparametric_ln": False, "sliding_window": 0,
            "dtype": spec["torch_dtype"]}
F8_MAX = 448.0                         # largest finite float8_e4m3fn


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 with one absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    if mode == "fp8":
        x, w = _q8(x, -1), _q8(w, -2)
    return jnp.matmul(x, w, precision=HI)


def _norm(x, scale, spec):
    eps = spec["rms_norm_eps"]
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv               # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]    # (S,1,hd/2)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _head_rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _block(h, lw, spec, mode):
    S = h.shape[0]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    a = f32["attn"]
    x = _norm(h, f32["ln1"]["scale"], spec)
    q = _mm(x, a["wq"], mode).reshape(S, H, hd)
    k = _mm(x, a["wk"], mode).reshape(S, KV, hd)
    v = _mm(x, a["wv"], mode).reshape(S, KV, hd)
    if spec["qk_norm"]:
        q = _head_rms(q, a["q_norm"], spec["qk_norm_eps"])
        k = _head_rms(k, a["k_norm"], spec["qk_norm_eps"])
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
    x = _norm(h, f32["ln2"]["scale"], spec)
    y = jax.nn.silu(_mm(x, m["w_gate"], mode)) * _mm(x, m["w_up"], mode)
    return h + _mm(y, m["w_down"], mode)


def logits(weights, spec: dict, tokens, mode: str = "ref"):
    """float32 logits (S, V) of one sequence ``tokens`` (S,)."""
    h = weights["embed"][tokens].astype(jnp.float32)
    blocks = {k: weights["blocks"][k] for k in ("attn", "mlp", "ln1", "ln2")}
    h, _ = jax.lax.scan(lambda c, lw: (_block(c, lw, spec, mode), None),
                        h, blocks)
    h = _norm(h, weights["final_norm"]["scale"], spec)
    return _mm(h, weights["embed"].astype(jnp.float32).T, mode)
