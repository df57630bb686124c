"""FLOPs of a dense transformer's forward work, per request or token,
from its configuration file (2 FLOPs per multiply-add).

Counted: every projection, MLP and output-head matmul, and the causal
attention products (q k^T and p v) over the positions each query sees.
Norms, softmax and rotary embedding are left out as negligible.
"""
from __future__ import annotations


def _dims(spec: dict):
    return (spec["num_hidden_layers"], spec["hidden_size"],
            spec["num_attention_heads"], spec["num_key_value_heads"],
            spec["head_dim"], spec["intermediate_size"],
            spec["vocab_size"])


def layer_matmul_params(spec: dict) -> int:
    """Weights one token multiplies through in one block."""
    _, d, H, KV, hd, f, _ = _dims(spec)
    return d * (H * hd + 2 * KV * hd) + H * hd * d + 3 * d * f


def attention_flops(spec: dict, n_keys: int, layers: int) -> int:
    """One query against ``n_keys`` keys in ``layers`` blocks."""
    _, _, H, _, hd, _, _ = _dims(spec)
    return 4 * H * hd * n_keys * layers


def forward_flops(spec: dict, S: int, *, head_rows: int) -> int:
    """A causal forward over ``S`` tokens through every block, with the
    output head applied to ``head_rows`` positions."""
    L, d, *_, V = _dims(spec)
    return (2 * S * L * layer_matmul_params(spec)
            + attention_flops(spec, S * (S + 1) // 2, L)
            + 2 * head_rows * d * V)


def prefill_flops(spec: dict, S: int) -> int:
    """A decode admission: the prompt, and the head for its last token."""
    return forward_flops(spec, S, head_rows=1)


def token_flops(spec: dict, n_keys: int) -> int:
    """One generated token whose query sees ``n_keys`` positions."""
    L, d, *_, V = _dims(spec)
    return (2 * L * layer_matmul_params(spec)
            + attention_flops(spec, n_keys, L) + 2 * d * V)

