"""Work of the decode attention kernel (``decode_attention``): one query
token per stream against the keys and values of its live positions only
(not the allocated cache length), so a later paged kernel is read
against the same work as today's."""
from __future__ import annotations

BYTES = 2                                  # bf16 cache and activations


def cost(spec: dict, contexts) -> tuple[int, int]:
    """(flops, bytes) of one decode query per entry of ``contexts`` (the
    number of positions it attends to) through every block: q k^T and
    p v; k and v of each live position read once, q read and the output
    written once."""
    L = spec["num_hidden_layers"]
    H, KV = spec["num_attention_heads"], spec["num_key_value_heads"]
    hd = spec["head_dim"]
    n = sum(contexts)
    flops = 4 * H * hd * n * L
    nbytes = (2 * KV * hd * n + 2 * H * hd * len(contexts)) * BYTES * L
    return flops, nbytes
