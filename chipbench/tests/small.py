"""Small sizes of the benchmark's cells, for CPU tests: the cells'
configurations and mixes with their widths and lengths cut so that a
whole run (warm-up, window, reference) takes seconds on the CPU."""
from __future__ import annotations

import dataclasses

from chipbench import model, traffic

SIZES = {"num_hidden_layers": 4, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "intermediate_size": 128, "vocab_size": 256}
PROGRAM = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
           "head_dim": 16, "d_ff": 128, "vocab_size": 256}


def config(name: str = "qwen3-1.7b"):
    """(configuration file at small sizes, the program's matching config)."""
    from repro.configs import get_config
    return ({**model.load_config(name), **SIZES},
            dataclasses.replace(get_config(name), **PROGRAM))


def decode_traffic() -> dict:
    """Short prompts and long outputs, so that the planted faults show at
    these widths (a random-weight model leans on its recent tokens); what
    the same faults read at the cell's own sizes is measured on the chip
    with ``limits.py --fault``."""
    return traffic.load("decode_backlog") | {
        "prompt_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 16,
                          "round_to": 4},
        "output_tokens": {"median": 24, "sigma": 0.3, "min": 16, "max": 40},
        "block": 8, "blocks": 4, "slots": 4, "decode_ctx": 64,
        "kv_blocks": 64, "check_requests": 4, "check_tail_s": 0.5}


def run(workload: str, seed: int, *, traffic_spec, cell,
        seconds: float = 1.0, name: str = "qwen3-1.7b") -> dict:
    """One whole run of the harness at small sizes: its result line."""
    from chipbench import run as harness
    spec, cfg = config(name)
    return harness.run_cell(workload, seed, seconds, False,
                            require_chip=False, spec=spec,
                            traffic=traffic_spec, cfg=cfg, cell=cell)


def outcome(workload: str, seed: int, *, traffic_spec, cell,
            seconds: float = 1.0, name: str = "qwen3-1.7b"):
    """(context, driver module, outcome) of one small run, for reading
    the reference's lower-precision control on the same answers."""
    from chipbench import run as harness
    spec, cfg = config(name)
    _, _, _, ctx = harness.prepare(workload, seed, seconds, False,
                                   require_chip=False, spec=spec,
                                   traffic=traffic_spec, cfg=cfg, cell=cell)
    driver = harness.driver(traffic_spec)
    try:
        return ctx, driver, driver.run(ctx)
    finally:
        ctx.compile_log.uninstall()
