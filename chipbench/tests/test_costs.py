"""Kernel FLOP and byte functions against hand counts."""
from chipbench.costs import decode_attention, model

SPEC = {"num_hidden_layers": 2, "hidden_size": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 2, "intermediate_size": 16,
        "vocab_size": 10}


def test_decode_attention_batch_reads_live_positions_only():
    # two streams seeing 5 and 7 positions: 12 keys per head
    flops, nbytes = decode_attention.cost(SPEC, [5, 7])
    assert flops == 4 * 4 * 2 * 12 * 2            # 4*H*hd*keys*layers
    # k and v: 12 positions x 2 KV heads x 2 dims x 2 = 96 values; q and
    # o: 2 streams x 4 heads x 2 dims x 2 = 32 values; x 2 B x 2 layers
    assert nbytes == (96 + 32) * 2 * 2


def test_model_flops_per_token_and_request():
    per_layer = 8 * (4 * 2 + 2 * 2 * 2) + 4 * 2 * 8 + 3 * 8 * 16
    assert model.layer_matmul_params(SPEC) == per_layer
    assert model.token_flops(SPEC, 3) == (2 * 2 * per_layer
                                          + 4 * 4 * 2 * 3 * 2 + 2 * 8 * 10)
    S = 4
    full = model.forward_flops(SPEC, S, head_rows=S)
    assert full == (2 * S * 2 * per_layer
                    + 4 * 4 * 2 * (S * (S + 1) // 2) * 2 + 2 * S * 8 * 10)
    assert model.prefill_flops(SPEC, S) == full - 2 * (S - 1) * 8 * 10
