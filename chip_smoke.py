#!/usr/bin/env python3
"""Serve qwen3-1.7b at its published widths on one TPU and check the answers.

    python chip_smoke.py [--seed N]

Run from a checkout (the program is imported from ``src/`` beside this
file). One process holds the chip for the whole run. Phases:

  device     the default JAX device must be a TPU; there is no CPU path.
  setup      qwen3-1.7b from the registry, nothing reduced (28 layers,
             d_model 2048, 16/8 heads of 128, d_ff 6144, vocab 151,936,
             tied embeddings, bf16), weights made from ``--seed``.
  fragment   clients at partition points 0, 4 and 8 served by GraftServer
             through a fixed re-aligned plan: align pools [p, 8) feeding
             a shared pool [8, 28), so depth-2 chains run.
  decode     one full-range pool, continuous batching at B=4 over the
             paged KV arena, streams of 16 tokens, half on a shared prompt.
  reference  every served result against a float32 forward of the same
             weights (plain jnp attention, highest matmul precision).

The run fails — non-zero exit and no result line — when a phase raises,
a numerics check fails, the kernels in use are not the Pallas ones, or
the server fell back to in-process finishes or decodes, or shed anything.
On success the last line is ``{"ok": true, "device": {...}}``.

The persistent compile cache is where ``JAX_COMPILATION_CACHE_DIR`` says,
else ``<checkout>/.jax_cache``; a second run prints lower compile seconds.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-1.7b"

# bf16 keeps 8 significant bits (unit roundoff 2^-9 ~ 2e-3), and a 28-layer
# forward rounds ~60 residual-branch outputs. Served in bf16 on a TPU v5e,
# the fragment logits of seed 0 were off the float32 ones by 1.6e-2
# relative L2 and 0.078 at most.
FRAG_REL_TOL = 5e-2          # ||served - ref|| / ||ref|| per request (3x)
LOGIT_ERR = 0.1              # one logit's error: the 0.078 measured, +28%
# If every served logit is within LOGIT_ERR of the float32 one, the served
# argmax is at most 2 * LOGIT_ERR below the reference maximum; a bf16 near
# tie may flip inside that margin and nowhere else. On the same run 122 of
# 128 decoded tokens were the float32 argmax (95%); a decode fault that
# still lands near the top shows up as a lower share.
DECODE_MARGIN = 2 * LOGIT_ERR   # 0.2
MIN_EXACT_SHARE = 0.9


def say(*args) -> None:
    print(*args, flush=True)


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@contextlib.contextmanager
def phase(name: str, results: dict):
    """Time one phase into ``results[name]``, with its backend compiles
    (persistent-cache hits included, at their read time) and seconds."""
    import jax.monitoring as mon
    log = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            log["compiles"] += 1
            log["compile_s"] += secs

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            log["cache_hits"] += 1

    mon.register_event_duration_secs_listener(on_duration)
    mon.register_event_listener(on_event)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        mon.unregister_event_duration_listener(on_duration)
        mon.unregister_event_listener(on_event)
    log["wall_s"] = time.perf_counter() - t0
    results[name] = log
    say(f"[{name}] wall {log['wall_s']:.1f}s, {log['compiles']} compiles "
        f"({log['cache_hits']} persistent-cache hits) in "
        f"{log['compile_s']:.1f}s")


def fallback_counts(report: dict) -> dict:
    """The server's escape hatches; each must stay at zero."""
    return {k: int(report[k]) for k in
            ("local_finishes", "decode_local", "shed_ingest", "shed_flush",
             "shed_decode")}


# ---------------------------------------------------------------- phases

def run_fragment_phase(cfg, book, params, *, points=(0, 4, 8),
                       shared_at: int = 8, batch: int = 4,
                       prompt_len: int = 16, seed: int = 0,
                       budget_ms: float = 60_000.0) -> dict:
    """Clients at ``points`` served by GraftServer through a fixed
    re-aligned plan: align pools [p, shared_at) and a shared pool
    [shared_at, L). Each client sends ``batch`` requests as one wave, so
    every pool batch is full and each packed program sees one shape."""
    from repro.core.fragment import Fragment
    from repro.serving.executor import GraftExecutor, ServeRequest
    from repro.serving.server import GraftServer
    from repro.serving.smoke import mixed_depth_plan
    from repro.serving.transport import InProcessTransport

    frags = [Fragment(cfg.name, p=p, t=budget_ms, q=30.0, client=f"c{p}")
             for p in points]
    plan = mixed_depth_plan(cfg, book, frags, s=shared_at, batch=batch)
    ex = GraftExecutor(plan, params, cfg, transport=InProcessTransport())
    rng = np.random.RandomState(seed)
    served = []
    try:
        server = GraftServer(ex, book=book).start()
        try:
            for f in frags:
                for _ in range(batch):
                    req = ServeRequest(client=f.client, tokens=rng.randint(
                        0, cfg.vocab_size, prompt_len).astype(np.int32))
                    server.submit(req, f.p, budget_ms)
                    served.append((req, f.p))
                if not server.join(timeout=900.0):
                    raise RuntimeError(f"fragment wave of {f.client} never "
                                       "drained")
            report = server.report()
        finally:
            server.stop(drain=False, timeout=10.0)
        pools = {f"{k[1]}-{k[2]}": s["n_batches"]
                 for k, s in ex.pool_stats().items()}
    finally:
        ex.close()
    return {"served": served, "report": report, "pools": pools,
            "fallbacks": fallback_counts(report)}


def run_decode_phase(cfg, book, params, *, batch: int = 4,
                     decode_ctx: int = 256, kv_block_tokens: int = 16,
                     n_streams: int = 8, max_new: int = 16,
                     prompt_len: int = 32, seed: int = 0,
                     budget_ms: float = 60_000.0) -> dict:
    """Continuous-batched decode on one full-range pool over the paged KV
    arena. Odd streams share one prompt (two full KV blocks), so later
    admissions reuse the first one's blocks."""
    from repro.core.fragment import Fragment
    from repro.serving.executor import GraftExecutor, ServeRequest
    from repro.serving.server import GraftServer
    from repro.serving.smoke import decode_plan
    from repro.serving.transport import InProcessTransport

    frags = [Fragment(cfg.name, p=0, t=budget_ms, q=30.0, client="d0")]
    plan = decode_plan(cfg, book, frags, batch=batch)
    ex = GraftExecutor(plan, params, cfg, transport=InProcessTransport(),
                       decode_ctx=decode_ctx,
                       kv_block_tokens=kv_block_tokens)
    rng = np.random.RandomState(seed + 1)
    shared = rng.randint(0, cfg.vocab_size, prompt_len).astype(np.int32)
    served = []
    try:
        server = GraftServer(ex, book=book).start()
        try:
            for i in range(n_streams):
                toks = shared if i % 2 else rng.randint(
                    0, cfg.vocab_size, prompt_len).astype(np.int32)
                req = ServeRequest(client="d0", tokens=toks,
                                   max_new_tokens=max_new,
                                   tpot_budget_ms=budget_ms)
                server.submit(req, 0, budget_ms)
                served.append(req)
            if not server.join(timeout=900.0):
                raise RuntimeError("decode streams never drained")
            report = server.report()
        finally:
            server.stop(drain=False, timeout=10.0)
        pool = next(s for s in ex.pool_stats().values() if s.get("kv"))
    finally:
        ex.close()
    return {"served": served, "report": report, "kv": pool["kv"],
            "steps": pool["decode_steps"], "admits": pool["decode_admits"],
            "fallbacks": fallback_counts(report)}


# -------------------------------------------------------------- reference

def reference_logits(cfg, params, tokens: np.ndarray) -> np.ndarray:
    """float32 logits of the monolithic forward over ``tokens`` (N, S):
    the same weights cast up, plain jnp attention, highest matmul
    precision — independent of the kernels and the serving path."""
    import jax
    import jax.numpy as jnp
    from repro import models as M
    from repro.kernels import ops

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with ops.use_impl("naive"), jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: M.forward(p, cfg32, t)[0])
        out = np.asarray(fwd(p32, jnp.asarray(tokens)))
    del p32
    return out


def check_fragments(cfg, params, served: list) -> dict:
    """Every fragment result against the float32 forward."""
    missing = [r.client for r, _ in served if r.result is None]
    if missing:
        return {"ok": False, "missing": missing}
    ref = reference_logits(cfg, params, np.stack([r.tokens for r, _ in served]))
    rel, err = [], []
    for (req, _), want in zip(served, ref):
        got = np.asarray(req.result, np.float32)
        rel.append(float(np.linalg.norm(got - want) / np.linalg.norm(want)))
        err.append(float(np.abs(got - want).max()))
    return {"ok": max(rel) <= FRAG_REL_TOL, "n": len(rel),
            "max_rel_err": max(rel), "max_abs_err": max(err),
            "tol": FRAG_REL_TOL}


def check_decode(cfg, params, served: list) -> dict:
    """Teacher-forced check of every decoded token: under the float32
    forward of prompt + served tokens, each served token is the argmax of
    its position or within ``DECODE_MARGIN`` of it, and at least
    ``MIN_EXACT_SHARE`` of them are the argmax."""
    missing = [i for i, r in enumerate(served) if not r.out_tokens]
    if missing:
        return {"ok": False, "missing": missing}
    S = len(served[0].tokens)
    seqs = np.stack([np.concatenate([r.tokens, r.out_tokens[:-1]])
                     for r in served]).astype(np.int32)
    ref = reference_logits(cfg, params, seqs)
    gaps, exact = [], 0
    for req, logits in zip(served, ref):
        for j, tok in enumerate(req.out_tokens):
            row = logits[S - 1 + j]
            gaps.append(float(row.max() - row[tok]))
            exact += int(row.argmax() == tok)
    return {"ok": (max(gaps) <= DECODE_MARGIN
                   and exact >= MIN_EXACT_SHARE * len(gaps)),
            "n_tokens": len(gaps), "exact": exact, "max_gap": max(gaps),
            "margin": DECODE_MARGIN, "min_exact_share": MIN_EXACT_SHARE}


# ------------------------------------------------------------------- main

def _import_program():
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no program at {src}; run this script "
                         "from a checkout of the repository")
    sys.path.insert(0, str(src))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _import_program()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: "
              f"{devs[0].platform}); this check runs on the chip only",
              file=sys.stderr)
        return 1
    from repro.core.costmodel import peak_rates
    from repro.kernels import ops
    from repro.serving.smoke import configure_compile_cache

    cache = configure_compile_cache(ROOT)
    dev = devs[0]
    say(f"[device] {dev.device_kind} x{len(devs)} ({dev.platform}), "
        f"jax {jax.__version__}, compile cache {cache}")
    say(f"[device] peaks {peak_rates(dev.device_kind)}")
    impl = ops.get_default_impl()
    say(f"[device] attention kernels: {impl}")

    results: dict = {}
    failures = []
    if impl != "pallas":
        failures.append(f"kernel impl is {impl!r}, not 'pallas'")
    try:
        with phase("setup", results):
            from repro.serving.smoke import smoke_setup
            cfg, book, params = smoke_setup(ARCH, published=True,
                                            seed=args.seed)
            jax.block_until_ready(params)
        leaves = jax.tree.leaves(params)
        say(f"[setup] {cfg.name}: {cfg.n_layers} layers, d_model "
            f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads x "
            f"{cfg.head_dim_}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
            f"{cfg.dtype}; {sum(a.size for a in leaves):,} params, "
            f"{sum(a.nbytes for a in leaves):,} bytes")

        with phase("fragment", results):
            frag = run_fragment_phase(cfg, book, params, seed=args.seed)
        say(f"[fragment] served {frag['report']['served']}/"
            f"{len(frag['served'])}, batches per pool {frag['pools']}, "
            f"fallbacks {frag['fallbacks']}")

        with phase("decode", results):
            dec = run_decode_phase(cfg, book, params, seed=args.seed)
        timing = dec["report"].get("decode", {})
        say(f"[decode] served {dec['report']['decode_served']}/"
            f"{len(dec['served'])} streams, {dec['report']['decode_tokens']} "
            f"tokens in {dec['steps']} batched steps and {dec['admits']} "
            f"admissions, tpot p50 {timing.get('tpot_p50_ms')} ms p99 "
            f"{timing.get('tpot_p99_ms')} ms, ttft p50 "
            f"{timing.get('ttft_p50_ms')} ms, prefix hits "
            f"{dec['kv']['prefix_hits']} ({dec['kv']['prefix_tokens_reused']}"
            f" tokens reused), fallbacks {dec['fallbacks']}")

        with phase("reference", results):
            fcheck = check_fragments(cfg, params, frag["served"])
            dcheck = check_decode(cfg, params, dec["served"])
        say(f"[reference] fragments {fcheck}")
        say(f"[reference] decode {dcheck}")
    except Exception as e:                  # any phase failing fails the run
        import traceback
        traceback.print_exc()
        failures.append(f"phase raised {type(e).__name__}: {e}")
    else:
        for name, part in (("fragment", frag), ("decode", dec)):
            bad = {k: v for k, v in part["fallbacks"].items() if v}
            if bad:
                failures.append(f"{name} fallbacks {bad}")
        if len(frag["served"]) != frag["report"]["served"]:
            failures.append("fragment requests went unserved")
        if len(dec["served"]) != dec["report"]["decode_served"]:
            failures.append("decode streams went unserved")
        if not fcheck["ok"]:
            failures.append(f"fragment numerics {fcheck}")
        if not dcheck["ok"]:
            failures.append(f"decode numerics {dcheck}")
    stats = dev.memory_stats() or {}
    say(f"[device] peak_bytes_in_use {stats.get('peak_bytes_in_use')}")
    say(f"[summary] compile s per phase "
        f"{ {k: round(v['compile_s'], 1) for k, v in results.items()} }")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAIL {f}", file=sys.stderr)
        return 1
    say(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
