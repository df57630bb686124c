"""Shared scaffolding for real-execution (smoke-scale) serving runs.

The executor tests, ``launch/serve.py --execute`` and the online-serving
example all need the same setup: a reduced model config, a profile book
built from its analytic layer costs, initialised parameters, and a fleet
of smoke fragments whose partition points are valid for the reduced
layer count. Centralised here so the pieces can't drift apart.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Optional

import numpy as np

from repro.core.costmodel import arch_layer_costs
from repro.core.fragment import Fragment
from repro.core.profiles import ProfileBook

DEFAULT_ARCH = "qwen3-1.7b"
DEFAULT_SEQ = 16


def configure_compile_cache(root: Path) -> str:
    """Point JAX's persistent compile cache at ``root/.jax_cache``, for an
    entry point run from the checkout ``root`` (never at import, so tests
    keep their own settings).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set. The path is fixed, so each run finds what the
    previous one wrote. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def smoke_setup(arch: str = DEFAULT_ARCH, *, seq_len: int = DEFAULT_SEQ,
                seed: int = 0, n_layers: Optional[int] = None,
                published: bool = False):
    """-> (cfg, book, params): everything an executor needs, smoke scale.

    ``n_layers`` deepens the reduced model beyond the default 2 blocks —
    multi-stage chains (align -> shared) need at least 3 boundaries to be
    interesting. ``published`` returns the registry config itself, at its
    published widths and dtype (``n_layers`` is then ignored): the chip
    smoke serves that."""
    import jax
    from repro import models as M
    from repro.configs import get_config, get_smoke_config, reduced

    if published:
        cfg = get_config(arch)
    else:
        cfg = get_smoke_config(arch)
        if n_layers is not None and n_layers != cfg.n_layers:
            cfg = reduced(get_config(arch), n_layers=n_layers)
    costs = dataclasses.replace(arch_layer_costs(cfg, seq_len=seq_len),
                                name=cfg.name)
    book = ProfileBook()
    book.add(costs)
    # one compiled program for the whole init (same values as eager),
    # so the weights are made on the device without per-op dispatch
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    return cfg, book, params


def smoke_fragments(cfg, n_clients: int = 3, *, rate: float = 30.0,
                    seed: int = 0) -> list[Fragment]:
    """A small fleet with partition points spread over the reduced model."""
    from repro.models import n_fragment_units
    rng = np.random.RandomState(seed)
    L = n_fragment_units(cfg)
    return [Fragment(cfg.name, p=int(rng.randint(0, L)),
                     t=float(40.0 + 40.0 * rng.rand()), q=rate,
                     client=f"c{i}")
            for i in range(n_clients)]


def smoke_requests(cfg, frags, *, seq_len: int = DEFAULT_SEQ,
                   seed: Optional[int] = None, rng=None) -> list:
    """[(ServeRequest, p), ...] with random token payloads per fragment."""
    from repro.serving.executor import ServeRequest
    if rng is None:
        rng = np.random.RandomState(seed or 0)
    return [(ServeRequest(
        client=f.client,
        tokens=rng.randint(0, cfg.vocab_size, seq_len).astype(np.int32)),
        f.p) for f in frags]


def mixed_depth_plan(cfg, book, frags, *, s: int = 1, batch: int = 4):
    """Hand-built ExecutionPlan with REAL depth-2 chains: clients with
    p < s run an alignment stage [p, s) then the shared pool [s, L);
    clients at p == s hit the shared pool directly.

    The analytic smoke cost book is so cheap that ``GraftPlanner`` always
    prefers solo batch-1 pools at this scale — but the runtime (executor,
    server, benches) must be exercised on the paper's aligned topology
    regardless of what the planner would pick, so this builds the grouped
    plan explicitly.
    """
    from repro.core.planner import ExecutionPlan
    from repro.core.profiles import Allocation, EMPTY_ALLOC
    from repro.core.repartition import GroupPlan, StagePlan
    from repro.models import n_fragment_units

    prof = book[cfg.name]
    L = n_fragment_units(cfg)
    assert all(f.p <= s for f in frags), "clients must start at p <= s"

    def alloc(start, end, b):
        lat = float(prof.latency_ms(start, end, b, 50))
        return Allocation(share=50, batch=b, n_instances=1,
                          latency_ms=lat, throughput=b / lat * 1e3,
                          resource=50.0)

    lead = min(frags, key=lambda f: f.t)
    shared = StagePlan(lead, s, L, lead.t / 2.0, alloc(s, L, batch))
    aligns = tuple(
        StagePlan(f, f.p, s, f.t / 2.0,
                  alloc(f.p, s, batch) if f.p < s else EMPTY_ALLOC)
        for f in frags)
    gp = GroupPlan(model=cfg.name, repartition_point=s, shared=shared,
                   aligns=aligns)
    return ExecutionPlan(plans=[gp], total_resource=gp.resource,
                         n_fragments_in=len(frags),
                         n_fragments_merged=len(frags),
                         schedule_time_s=0.0)


def check_against_monolithic(cfg, params, reqs, *, atol=5e-5, rtol=1e-3):
    """Assert each served result equals the un-fragmented forward pass."""
    from repro import models as M
    for req, _p in reqs:
        want, _ = M.forward(params, cfg, np.asarray(req.tokens)[None])
        np.testing.assert_allclose(req.result, np.asarray(want[0]),
                                   atol=atol, rtol=rtol)


# ---------------------------------------------------------------------------
# route smoke: weighted routing + cross-front-end work stealing
# ---------------------------------------------------------------------------

def run_route_smoke(*, arch: str = DEFAULT_ARCH, seq_len: int = DEFAULT_SEQ,
                    seed: int = 0, n_hot: int = 4,
                    budget_ms: float = 5000.0, log=None) -> dict:
    """Blocking CI smoke: the routing subsystem end-to-end.

    Two front-ends over one shared pool under the weighted router. One
    front-end is wedged mid-traffic (drivers stop consuming, host marked
    unhealthy) with a skewed burst queued against it — the survivor must
    STEAL the queued-not-in-flight work through the fleet balancer and
    complete it with exact numerics, nothing shed and nothing doubled.
    Returns the fleet report (with ``numerics_ok``); raises on a
    stranded run."""
    import time

    from repro.serving.executor import GraftExecutor, ServeRequest
    from repro.serving.fleet import GraftFleet
    from repro.serving.router import rendezvous_route
    from repro.serving.transport import InProcessTransport

    say = log if log is not None else (lambda *_: None)
    cfg, book, params = smoke_setup(arch, seq_len=seq_len, seed=seed,
                                    n_layers=3)
    # one client per front-end under HRW, all entering the shared pool
    fes = ["fe0", "fe1"]
    frags, got, i = [], {fe: 0 for fe in fes}, 0
    while min(got.values()) < 1 and i < 10_000:
        name = f"rs{i}"
        fe = rendezvous_route(name, fes)
        if got[fe] < 1:
            got[fe] += 1
            frags.append(Fragment(cfg.name, p=1, t=budget_ms, q=30.0,
                                  client=name))
        i += 1
    plan = mixed_depth_plan(cfg, book, frags, s=1, batch=4)
    ex = GraftExecutor(plan, params, cfg, transport=InProcessTransport())
    fleet = GraftFleet(ex, n_frontends=len(fes), book=book).start()
    rng = np.random.RandomState(seed)

    def _reqs(frag, n):
        return [(ServeRequest(
            client=frag.client,
            tokens=rng.randint(0, cfg.vocab_size,
                               seq_len).astype(np.int32)), frag.p)
            for _ in range(n)]

    t0 = time.monotonic()
    try:
        warm = [r for f in frags for r in _reqs(f, 1)]
        for req, p in warm:
            fleet.submit(req, p, budget_ms)
        if not fleet.join(timeout=300.0):
            raise RuntimeError("route smoke: warm round never drained")
        table = fleet.routing_table([f.client for f in frags])
        hot = frags[0]
        victim_fe = table[hot.client]
        victim = fleet.frontend(victim_fe)
        say(f"[route-smoke] wedging {victim_fe} with {n_hot} queued "
            f"requests; survivor must steal")
        for drv in victim._drivers.values():
            drv.batcher.pause()
        doomed = _reqs(hot, n_hot)
        for req, p in doomed:          # accepted by victim BEFORE the mark
            victim.submit(req, p, budget_ms)
        deadline = time.monotonic() + 30.0
        while victim.n_queued < len(doomed):
            if time.monotonic() > deadline:
                raise RuntimeError("route smoke: burst never queued on "
                                   "the wedged front-end")
            time.sleep(0.005)
        fleet.set_health(victim_fe, False)
        # the next control tick priority-steals the wedged queue
        while fleet.stats["steals"] < len(doomed):
            if time.monotonic() > deadline:
                raise RuntimeError("route smoke: nothing stolen from the "
                                   "wedged front-end")
            time.sleep(0.005)
        if not fleet.join(timeout=300.0):
            raise RuntimeError("route smoke: stolen work never completed")
        for drv in victim._drivers.values():
            drv.batcher.resume()
        fleet.set_health(victim_fe, True)
        report = fleet.report()
    finally:
        fleet.stop(drain=False, timeout=10.0)
        ex.close()
    report["wall_s"] = time.monotonic() - t0
    done = warm + doomed
    try:
        check_against_monolithic(cfg, params, done)
        report["numerics_ok"] = True
    except AssertionError as e:
        report["numerics_ok"] = False
        report["numerics_error"] = str(e)[:500]
    report["numerics_checked"] = len(done)
    say(f"[route-smoke] served={report['served']} "
        f"steals={report['steals']} shed={report['shed']} "
        f"router={report['router']} "
        f"numerics_ok={report['numerics_ok']} "
        f"({report['wall_s']:.1f}s)")
    return report


# ---------------------------------------------------------------------------
# decode smoke: paged-KV continuous batching vs the unbatched reference
# ---------------------------------------------------------------------------

def decode_plan(cfg, book, frags, *, batch: int = 4):
    """Single full-range pool — the decode topology (the paged cache
    lives pool-side, so decode needs one pool spanning the model)."""
    flat = [dataclasses.replace(f, p=0) for f in frags]
    return mixed_depth_plan(cfg, book, flat, s=0, batch=batch)


def disagg_plan(cfg, book, frags, *, batch: int = 4):
    """The decode topology split across roles: the full-range pool is
    re-roled to prefill and a decode-role pool of the same range rides
    along (``ExecutionPlan.with_disagg``) — prompt prefill runs on one
    pool, the KV blocks cross the transport, and the decode pool owns
    the resident streams."""
    from repro.models import n_fragment_units
    plan = decode_plan(cfg, book, frags, batch=batch)
    return plan.with_disagg(cfg.name, n_fragment_units(cfg), batch=batch)


def reference_decode(cfg, params, tokens, max_new: int) -> list:
    """Unbatched greedy decode: prefill + one token at a time, no cache
    manager — THE numerics the serving path must reproduce exactly."""
    import jax.numpy as jnp
    from repro.models.decode import decode_step, prefill
    toks = np.asarray(tokens, np.int32).reshape(-1)
    ctx = int(toks.shape[0]) + max_new
    logits, cache = prefill(params, cfg, jnp.asarray(toks)[None],
                            cache_seq=ctx)
    out = [int(jnp.argmax(logits[0, -1]))]
    while len(out) < max_new:
        logits, cache = decode_step(params, cfg, cache,
                                    jnp.asarray([[out[-1]]], jnp.int32))
        out.append(int(jnp.argmax(logits[0, -1])))
    return out


def check_decode_against_reference(cfg, params, served: list) -> None:
    """``served``: [(ServeRequest, max_new), ...] with ``out_tokens``
    filled in. Greedy decode must match the reference token-for-token."""
    for req, max_new in served:
        want = reference_decode(cfg, params, req.tokens, max_new)
        got = list(req.out_tokens or [])
        assert got == want, (
            f"decode mismatch for {req.client}: served {got} != "
            f"reference {want}")


def run_decode_smoke(*, arch: str = DEFAULT_ARCH, n_clients: int = 3,
                     n_requests: int = 12, seq_len: int = 12,
                     max_new: int = 5, decode_ctx: int = 64,
                     seed: int = 0, budget_ms: float = 4000.0,
                     tpot_ms: float = 2000.0, log=None) -> dict:
    """Blocking CI smoke: run the event-driven server's continuous-
    batching decode path end-to-end in-process and check every stream's
    tokens against the unbatched reference. Returns the server report
    (with ``numerics_ok``); raises on a stranded run."""
    import time

    from repro.serving.executor import GraftExecutor, ServeRequest
    from repro.serving.server import GraftServer
    from repro.serving.transport import InProcessTransport

    say = log if log is not None else (lambda *_: None)
    cfg, book, params = smoke_setup(arch, seq_len=seq_len, seed=seed)
    frags = smoke_fragments(cfg, n_clients, rate=30.0, seed=seed)
    plan = decode_plan(cfg, book, frags, batch=max(n_clients, 2))
    # small blocks so the smoke prompts span FULL blocks — the prefix
    # index only shares full (or clean-partial) blocks, so default-sized
    # blocks would swallow the whole prompt into one unshareable partial
    ex = GraftExecutor(plan, params, cfg, transport=InProcessTransport(),
                       decode_ctx=decode_ctx, kv_block_tokens=4)
    server = GraftServer(ex, book=book).start()
    served: list = []
    say(f"[decode-smoke] {cfg.name}: {n_requests} streams x {max_new} "
        f"tokens over {n_clients} clients, decode_ctx={decode_ctx}")
    t0 = time.monotonic()
    try:
        for i in range(n_requests):
            f = frags[i % len(frags)]
            # half the streams share a per-client prompt (exercises the
            # paged cache's prefix sharing), half are fresh
            if i % 2 == 0:
                crng = np.random.RandomState(seed * 131 + i)
                toks = crng.randint(0, cfg.vocab_size,
                                    seq_len).astype(np.int32)
            else:
                crng = np.random.RandomState(seed * 977
                                             + (i % len(frags)))
                toks = crng.randint(0, cfg.vocab_size,
                                    seq_len).astype(np.int32)
            req = ServeRequest(client=f.client, tokens=toks,
                               max_new_tokens=max_new,
                               tpot_budget_ms=tpot_ms)
            server.submit(req, 0, budget_ms)
            served.append((req, max_new))
            time.sleep(0.01)
        if not server.join(timeout=600.0):
            raise RuntimeError("decode smoke never drained")
        report = server.report()
        kv = {}
        for s in ex.pool_stats().values():
            if s.get("kv"):
                kv = s["kv"]
    finally:
        server.stop(drain=False, timeout=10.0)
        ex.close()
    report["wall_s"] = time.monotonic() - t0
    done = [(r, m) for r, m in served if r.out_tokens is not None]
    try:
        check_decode_against_reference(cfg, params, done)
        report["numerics_ok"] = True
    except AssertionError as e:
        report["numerics_ok"] = False
        report["numerics_error"] = str(e)[:500]
    report["numerics_checked"] = len(done)
    report["kv"] = kv
    say(f"[decode-smoke] served={report['decode_served']} "
        f"local={report['decode_local']} "
        f"prefix_hits={kv.get('prefix_hits', 0)} "
        f"numerics_ok={report['numerics_ok']} "
        f"({report['wall_s']:.1f}s)")
    return report


# ---------------------------------------------------------------------------
# disagg smoke: prefill/decode pool split with cross-pool KV handoff
# ---------------------------------------------------------------------------

def run_disagg_smoke(*, arch: str = DEFAULT_ARCH, n_clients: int = 3,
                     n_requests: int = 10, seq_len: int = 12,
                     max_new: int = 5, decode_ctx: int = 64,
                     seed: int = 0, budget_ms: float = 4000.0,
                     tpot_ms: float = 2000.0, log=None) -> dict:
    """Blocking CI smoke: the disaggregated serve loop end-to-end.

    A prefill-role pool and a decode-role pool over the same range; the
    server's two-phase admit runs prompt prefill on one and hands the KV
    blocks to the other over the transport. Every stream must match the
    unbatched reference token-for-token AND at least one cross-pool KV
    handoff must actually have happened (otherwise the split silently
    degenerated to decode-pool self-prefill). Raises on a stranded run."""
    import time

    from repro.serving.executor import GraftExecutor, ServeRequest
    from repro.serving.server import GraftServer
    from repro.serving.transport import InProcessTransport

    say = log if log is not None else (lambda *_: None)
    cfg, book, params = smoke_setup(arch, seq_len=seq_len, seed=seed)
    frags = smoke_fragments(cfg, n_clients, rate=30.0, seed=seed)
    plan = disagg_plan(cfg, book, frags, batch=max(n_clients, 2))
    ex = GraftExecutor(plan, params, cfg, transport=InProcessTransport(),
                       decode_ctx=decode_ctx, kv_block_tokens=4,
                       decode_disagg=True)
    server = GraftServer(ex, book=book).start()
    served: list = []
    say(f"[disagg-smoke] {cfg.name}: {n_requests} streams x {max_new} "
        f"tokens, prefill pool -> KV frame -> decode pool")
    t0 = time.monotonic()
    try:
        rng = np.random.RandomState(seed)
        for i in range(n_requests):
            f = frags[i % len(frags)]
            # half the streams repeat a per-client prompt so the handoff
            # path exercises prefix sharing ACROSS the hop too
            if i % 2 == 0:
                crng = np.random.RandomState(seed * 131 + i)
            else:
                crng = np.random.RandomState(seed * 977 + (i % len(frags)))
            toks = crng.randint(0, cfg.vocab_size, seq_len).astype(np.int32)
            req = ServeRequest(client=f.client, tokens=toks,
                               max_new_tokens=max_new,
                               tpot_budget_ms=tpot_ms)
            server.submit(req, 0, budget_ms)
            served.append((req, max_new))
            time.sleep(0.01)
        if not server.join(timeout=600.0):
            raise RuntimeError("disagg smoke never drained")
        report = server.report()
        pool_kv = {}
        for key, s in ex.pool_stats().items():
            if s.get("kv"):
                pool_kv[s.get("role", "both")] = s["kv"]
    finally:
        server.stop(drain=False, timeout=10.0)
        ex.close()
    report["wall_s"] = time.monotonic() - t0
    done = [(r, m) for r, m in served if r.out_tokens is not None]
    try:
        check_decode_against_reference(cfg, params, done)
        report["numerics_ok"] = True
    except AssertionError as e:
        report["numerics_ok"] = False
        report["numerics_error"] = str(e)[:500]
    report["numerics_checked"] = len(done)
    report["pool_kv"] = pool_kv
    if report["kv_handoffs"] < 1:
        raise RuntimeError(
            "disagg smoke: no cross-pool KV handoff happened "
            f"(kv_handoffs={report['kv_handoffs']}, "
            f"decode_local={report['decode_local']})")
    say(f"[disagg-smoke] served={report['decode_served']} "
        f"handoffs={report['kv_handoffs']} "
        f"handoff_ms={report['kv_handoff_ms']:.2f} "
        f"local={report['decode_local']} "
        f"numerics_ok={report['numerics_ok']} "
        f"({report['wall_s']:.1f}s)")
    return report
