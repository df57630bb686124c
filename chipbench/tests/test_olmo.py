"""OLMo-1B on the CPU at a small multi-head size (4 query and 4 key/value
heads, so one key/value head per query head as at the published widths):
its reference computes the program's model, prefill then paged decode
through a pool gives the reference's logits, and a whole small run of
``olmo-decode-backlog`` is correct when sound and not correct under each
planted fault or with the reference's fp8 control in the program's place.
"""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import faults, model
from chipbench.references import olmo
from chipbench.tests import small

WORKLOAD = "olmo-decode-backlog"
CELL = json.loads((Path(__file__).resolve().parents[1] / "cells"
                   / f"{WORKLOAD}.json").read_text())
SIZES = {"num_hidden_layers": 4, "hidden_size": 64,
         "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
         "intermediate_size": 128, "vocab_size": 256}
PROGRAM = {"n_layers": 4, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
           "head_dim": 16, "d_ff": 128, "vocab_size": 256}
# float32 on both sides, with different summation orders: agreement to
# float32 rounding over four layers and a 256-entry head
TOL = 2e-4


def config(dtype: str = "bfloat16"):
    """(configuration file at the small size, the program's config)."""
    from repro.configs import get_config
    spec = {**model.load_config("olmo-1b"), **SIZES, "torch_dtype": dtype}
    cfg = dataclasses.replace(get_config("olmo-1b"), dtype=dtype, **PROGRAM)
    return spec, cfg


def _ref(params, spec, toks, mode="ref"):
    return np.asarray(jax.jit(lambda w, t: olmo.logits(w, spec, t, mode))(
        params, jnp.asarray(toks)))


def _rel(a, b):
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def test_reference_matches_program_forward():
    from repro import models as M
    from repro.kernels import ops
    spec, cfg = config("float32")
    params = model.make_weights(spec, 2**33 + 15)
    model.check_layout(spec, cfg, params)
    assert model.n_params(spec) == cfg.n_params()
    toks = np.random.default_rng(0).integers(0, spec["vocab_size"], 24)
    with ops.use_impl("naive"), jax.default_matmul_precision("highest"):
        want = np.asarray(M.forward(params, cfg, jnp.asarray(toks)[None])[0][0])
    np.testing.assert_allclose(_ref(params, spec, toks), want, rtol=TOL,
                               atol=TOL)
    assert _rel(_ref(params, spec, toks, "fp8"), want) > 1e-3


def _pool(cfg, params, decode_ctx=64):
    """One full-range decode pool as the benchmark deploys it, behind the
    wire protocol: (its instance, a handle that reaches it)."""
    from repro.core.costmodel import arch_layer_costs
    from repro.core.fragment import Fragment
    from repro.core.plandiff import plan_pools
    from repro.core.profiles import ProfileBook
    from repro.serving.executor import (FragmentInstance, PoolHandle,
                                        PoolService, pool_endpoint)
    from repro.serving.smoke import decode_plan
    from repro.serving.transport import InProcessTransport
    book = ProfileBook()
    book.add(dataclasses.replace(arch_layer_costs(cfg), name=cfg.name))
    plan = decode_plan(cfg, book, [Fragment(cfg.name, p=0, t=1e6, q=1.0,
                                            client="batch")], batch=3)
    (key, spec), = plan_pools(plan).items()
    inst = FragmentInstance(params, cfg, spec, decode_ctx=decode_ctx,
                            kv_blocks=64, kv_block_tokens=4)
    transport = InProcessTransport()
    transport.serve(pool_endpoint(key), PoolService(inst).handle)
    return inst, PoolHandle(key, transport.connect(pool_endpoint(key)))


def test_paged_decode_matches_reference_forward(monkeypatch):
    """Two streams, the second admitted while the first decodes: every
    logit row the pool's prefill and batched steps produced equals the
    reference's full forward over prompt and served tokens. The rows are
    taken inside the pool's own programs, where ``faults.py`` plants."""
    from repro.serving import executor
    spec, cfg = config("float32")
    params = model.make_weights(spec, 2**33 + 16)
    runs = []                        # last-position logits (B, V) per run
    step, prefill = executor.decode_step, executor.prefill

    def keep(logits):
        jax.debug.callback(lambda x: runs.append(np.asarray(x)),
                           logits[:, -1], ordered=True)

    def rec_step(params, cfg, cache, tokens):
        out = step(params, cfg, cache, tokens)
        keep(out[0])
        return out

    def rec_prefill(params, cfg, tokens, **kw):
        out = prefill(params, cfg, tokens, **kw)
        keep(out[0])
        return out

    monkeypatch.setattr(executor, "decode_step", rec_step)
    monkeypatch.setattr(executor, "prefill", rec_prefill)
    inst, handle = _pool(cfg, params)
    rng = np.random.default_rng(3)
    prompts = {701: rng.integers(0, cfg.vocab_size, 11).astype(np.int32),
               702: rng.integers(0, cfg.vocab_size, 7).astype(np.int32)}
    served = {rid: [] for rid in prompts}
    rows = {rid: [] for rid in prompts}

    def admit(rid, n):
        r = handle.decode_admit(rid, "batch", prompts[rid], n,
                                sig=("olmo", rid))
        jax.effects_barrier()
        assert r["admitted"]
        served[rid].append(r["tok"])
        rows[rid].append(runs[-1][0])

    def steps(n):
        for _ in range(n):
            slots = {s["rid"]: i for i, s in enumerate(inst._slots) if s}
            events = handle.decode_step()["events"]
            jax.effects_barrier()
            for ev in events:
                served[ev["rid"]].append(ev["tok"])
                rows[ev["rid"]].append(runs[-1][slots[ev["rid"]]])

    admit(701, 9)
    steps(3)
    admit(702, 6)
    steps(5)
    assert [len(served[r]) for r in (701, 702)] == [9, 6]
    for rid, toks in prompts.items():
        S = len(toks)
        seq = np.concatenate([toks, served[rid][:-1]])
        got = np.stack(rows[rid])
        want = _ref(params, spec, seq)[S - 1:]
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)
        assert list(got.argmax(-1)) == served[rid]
        assert _rel(_ref(params, spec, seq, "fp8")[S - 1:], got) > 1e-3
    handle.close()


def _run(seed, seconds=1.0):
    from chipbench import run as harness
    spec, cfg = config()
    return harness.run_cell(WORKLOAD, seed, seconds, False,
                            require_chip=False, spec=spec,
                            traffic=small.decode_traffic(), cfg=cfg,
                            cell=CELL)


def _fails_a_limit(numbers: dict) -> bool:
    return any(numbers[k] > v for k, v in CELL["limits"].items())


def test_sound_run_is_correct():
    line = _run(3_000_015_101)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == set(CELL["limits"])
    assert set(line["metrics"]) == {"decode_tok_s", "setup_s"}
    assert line["metrics"]["decode_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    undo = faults.plant(fault)
    try:
        line = _run(3_000_015_102)
    finally:
        undo()
    assert not line["correct"]
    assert _fails_a_limit({k: c["value"] for k, c in line["checks"].items()})


def test_fp8_control_fails_a_limit():
    from chipbench import run as harness
    spec, cfg = config()
    traffic = small.decode_traffic()
    _, _, _, ctx = harness.prepare(WORKLOAD, 3_000_015_103, 3.0, False,
                                   require_chip=False, spec=spec,
                                   traffic=traffic, cfg=cfg, cell=CELL)
    driver = harness.driver(traffic)
    try:
        out = driver.run(ctx)
    finally:
        ctx.compile_log.uninstall()
    assert out.correct
    assert _fails_a_limit(driver.control(ctx, out.served))
