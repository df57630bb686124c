"""Offline batch generation through continuous-batched decode.

Every request of the mix is queued at once on one full-range decode pool
(``GraftServer`` over a ``GraftExecutor`` with in-process transport), so
the queue never drains in the window. The window opens once every slot
holds a stream and lasts ``--seconds``; ``decode_tok_s`` is the pool's
own ``decode_tokens`` counter over the window, read through the pool's
stats op at both edges (each read waits for the step in flight, so both
edges fall between steps).

The tokens each stream receives are recorded as the pool's admission and
step replies carry them back over the transport. Correctness: the server
keeps serving for the mix's ``check_tail_s`` after the window closes, so
that the check compares some hundreds of served tokens; then a seeded
sample of the streams that received tokens (with the one that received
most) is teacher-forced through the float32 reference over prompt +
served tokens. Per served token the gap is how far its reference logit
lies below the reference's best, in standard deviations of the
reference's logits there; the cell file's ``limits`` say which of
``max_gap`` (the widest), ``mean_gap`` (over all compared tokens) and
``miss_share`` (the share that is not the reference's top token) are
held, and under what. A stream that finished must have been handed
exactly the tokens its replies carried.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import defaultdict

import numpy as np

from chipbench import traffic as traffic_mod
from chipbench.costs import decode_attention, model as model_cost
from chipbench.drivers.common import (Ctx, Outcome, delta, free_program,
                                      gaps_fn, join_server,
                                      memory_peak_bytes, pool_counters,
                                      sample, timed_request_class, traced)

CLIENT = "batch"


class TokenLog:
    """Tokens per request id, as the decode pool's replies carried them
    (admission replies carry a stream's first token, step replies one
    token per resident stream)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._tokens: dict = defaultdict(list)

    def attach(self, ex) -> None:
        """Record every reply of the pool handles ``ex`` opens from now
        on (the server opens its own lazily)."""
        opener = ex.open_handle

        def open_handle(key):
            h = opener(key)
            admit, step = h.decode_admit, h.decode_step

            def decode_admit(req_id, *a, **kw):
                r = admit(req_id, *a, **kw)
                if r.get("admitted"):
                    self._add(req_id, r["tok"])
                return r

            def decode_step():
                r = step()
                for ev in r.get("events", []):
                    if "tok" in ev:
                        self._add(ev["rid"], ev["tok"])
                return r

            h.decode_admit, h.decode_step = decode_admit, decode_step
            return h

        ex.open_handle = open_handle

    def _add(self, rid, tok) -> None:
        with self._lock:
            self._tokens[rid].append(int(tok))

    def snapshot(self) -> dict:
        with self._lock:
            return {k: list(v) for k, v in self._tokens.items()}


def _setup(ctx: Ctx):
    from repro.core.costmodel import arch_layer_costs
    from repro.core.fragment import Fragment
    from repro.core.profiles import ProfileBook
    from repro.serving.executor import GraftExecutor
    from repro.serving.server import GraftServer
    from repro.serving.smoke import decode_plan
    from repro.serving.transport import InProcessTransport

    t = ctx.traffic
    cfg = ctx.cfg
    book = ProfileBook()
    book.add(dataclasses.replace(arch_layer_costs(cfg), name=cfg.name))
    frags = [Fragment(cfg.name, p=0, t=t["ttft_budget_ms"], q=1.0,
                      client=CLIENT)]
    plan = decode_plan(cfg, book, frags, batch=t["slots"])
    ex = GraftExecutor(plan, ctx.params, cfg, transport=InProcessTransport(),
                       decode_ctx=t["decode_ctx"], kv_blocks=t["kv_blocks"],
                       kv_block_tokens=t["kv_block_tokens"])
    log = TokenLog()
    log.attach(ex)
    return ex, GraftServer(ex, book=book).start(), log


def _submit(server, Req, toks, max_new, t):
    req = Req(client=CLIENT, tokens=np.asarray(toks, np.int32),
              max_new_tokens=int(max_new),
              tpot_budget_ms=t["tpot_budget_ms"])
    req.rid = server.submit(req, 0, t["ttft_budget_ms"])
    return req


def _warm(ctx: Ctx, server, Req) -> None:
    """Every prompt length the mix can send, admitted once, in waves of
    ``slots`` streams; the first wave also runs one batched step."""
    t = ctx.traffic
    rng = np.random.default_rng([ctx.seed, 1])
    lengths = traffic_mod.prompt_lengths(t)
    for i in range(0, len(lengths), t["slots"]):
        for S in lengths[i:i + t["slots"]]:
            _submit(server, Req, rng.integers(0, ctx.cfg.vocab_size, S),
                    2 if i == 0 else 1, t)
        if not server.join(timeout=900.0):
            raise RuntimeError("decode warm-up never drained")


def _work(spec: dict, reqs: list, before: dict, after: dict) -> dict:
    """Decode-attention and model work of the tokens the window served:
    the prompts of streams admitted in it, and every step token (token j
    >= 2 of a stream comes from a query at position S + j - 2, which sees
    S + j - 1 positions)."""
    ctxs, prefills = [], []
    for r in reqs:
        n0, n1 = len(before.get(r.rid, ())), len(after.get(r.rid, ()))
        S = len(r.tokens)
        if n0 == 0 and n1 > 0:
            prefills.append(S)
        ctxs += [S + j - 1 for j in range(max(n0, 1) + 1, n1 + 1)]
    attn = decode_attention.cost(spec, ctxs)
    return {"decode_attention_flops": attn[0],
            "decode_attention_bytes": attn[1],
            "model_flops": sum(model_cost.prefill_flops(spec, S)
                               for S in prefills)
            + sum(model_cost.token_flops(spec, c) for c in ctxs)}


def run(ctx: Ctx) -> Outcome:
    t = ctx.traffic
    if t["arrivals"]["process"] != "backlog":
        raise ValueError("decode_backlog serves a backlog: every request "
                         "due at once")
    Req = timed_request_class()
    ex, server, log = _setup(ctx)
    try:
        _warm(ctx, server, Req)
        ctx.say(f"[setup] warm: {ctx.compile_log.snapshot()}")
        reqs = [_submit(server, Req, r.prompt, r.max_new, t)
                for r in traffic_mod.requests(t, ctx.seed,
                                              ctx.cfg.vocab_size)]
        key = next(iter(ex.pool_specs()))
        drv = server.driver(key)
        deadline = time.monotonic() + 600.0
        while drv.decode_active < t["slots"]:
            if time.monotonic() > deadline:
                raise RuntimeError("decode slots never filled")
            time.sleep(0.002)
        out: dict = {}
        c0 = pool_counters(ex)
        t0 = time.monotonic()
        tok0 = log.snapshot()
        with traced(ctx, out):
            time.sleep(ctx.seconds)
            c1 = pool_counters(ex)
            t1 = time.monotonic()
            tok1 = log.snapshot()
        peak = memory_peak_bytes()
        time.sleep(max(t1 + t["check_tail_s"] - time.monotonic(), 0.0))
        t_chk = time.monotonic()
        tok_chk = log.snapshot()
        report = server.report()
    finally:
        join_server(server)
    d = delta(c0, c1)[key]
    window = t1 - t0
    in_window = ctx.compile_log.between(t0, t1)
    if in_window:
        ctx.say(f"[window] compiles (s after open, program, s): {in_window}")
    readings = {
        "window_s": window, "window_end_s": t1 - ctx.t_start,
        "slots": t["slots"],
        "decode_tokens": d["decode_tokens"],
        "decode_steps": d["decode_steps"],
        "decode_admits": d["decode_admits"],
        "compiles_in_window": len(in_window),
        "peak_flops": ctx.peaks["flops_per_s"],
        "peak_bytes_per_s": ctx.peaks["hbm_bytes_per_s"],
        **_work(ctx.spec, reqs, tok0, tok1)}
    served = [(r, tok_chk[r.rid]) for r in reqs if tok_chk.get(r.rid)]
    mismatched = sum(1 for r, toks in served if r.out_tokens is not None
                     and getattr(r, "t_done", 1e30) <= t_chk
                     and list(r.out_tokens) != toks)
    failed = mismatched + sum(int(report[k]) for k in (
        "decode_local", "shed_decode", "shed_ingest", "shed_flush"))
    free_program(ex)
    del ex, server
    checks, readings["numbers"] = check(ctx, served)
    correct = failed == 0 and bool(served) and all(
        c["value"] <= c["limit"] for c in checks.values())
    return Outcome(e2e={"decode_tok_s": d["decode_tokens"] / window,
                        "setup_s": t0 - ctx.t_start},
                   readings=readings, checks=checks, correct=correct,
                   attempted=len(served), failed=failed,
                   memory_peak_bytes=peak, trace=out.get("trace"),
                   served=served)


def teacher_forced(ctx: Ctx, served: list, mode: str = "ref") -> list:
    """Per sampled stream, the gaps of its served tokens (or, with
    ``mode`` the control, of the tokens the control puts first)."""
    import jax.numpy as jnp
    t = ctx.traffic
    rng = np.random.default_rng([ctx.seed, 2])
    picked = sample(rng, served, t["check_requests"],
                    longest=lambda rt: len(rt[1]))
    fn = gaps_fn(ctx.spec, mode)
    n = t["decode_ctx"]
    out = []
    for r, gen in picked:
        S = len(r.tokens)
        seq = np.zeros(n, np.int32)
        seq[:S + len(gen) - 1] = np.concatenate([r.tokens, gen[:-1]])
        tgt = np.zeros(n, np.int32)
        tgt[S - 1:S - 1 + len(gen)] = gen
        g = fn(ctx.params, jnp.asarray(seq), jnp.asarray(tgt),
               S - 1, S - 1 + len(gen))
        out.append(np.asarray(g)[S - 1:S - 1 + len(gen)])
    return out


def numbers(per: list) -> dict:
    """The numbers a check may hold, over every compared token."""
    g = np.concatenate(per)
    return {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
            "miss_share": float(np.mean(g > 0))}


def control(ctx: Ctx, served: list) -> dict:
    """The check's numbers with the reference's fp8 control in the
    program's place, on the same prompts and tokens."""
    return numbers(teacher_forced(ctx, served, "fp8"))


def check(ctx: Ctx, served: list) -> tuple[dict, dict]:
    """({held number: value and limit}, every number)."""
    limits = ctx.cell["limits"]
    if not served:
        return {k: {"value": float("inf"), "limit": v}
                for k, v in limits.items()}, {}
    per = teacher_forced(ctx, served)
    got = numbers(per)
    ctx.say(f"[check] {len(per)} streams, {sum(map(len, per))} served "
            f"tokens: {got}")
    return {k: {"value": got[k], "limit": v}
            for k, v in limits.items()}, got
