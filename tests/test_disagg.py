"""Prefill/decode pool disaggregation.

Four layers, tested bottom-up:

  * plan layer — role-qualified pool keys, ``with_disagg`` plan
    construction, role-aware diffs (a role flip is a REBATCH, never a
    teardown), and the orphan rule (a decode pool must keep a feeder);
  * KV handoff — ``PagedKVCache.export_prefix`` / ``import_prefix``
    across two arenas preserve the chain keys, so prefix sharing (and
    COW refcounting) survives the hop; the transport's KV frame
    validates on decode;
  * serving — the two-phase admit (prefill pool -> KV frame -> decode
    pool) is token-exact against BOTH the single-pool continuous path
    and the unbatched reference;
  * faults — a dead prefill pool degrades to decode-pool self-prefill
    (typed error observed, nothing stranded), and the controller's
    ``disagg_pressure`` trigger arms/disarms like the other signals.
"""
import dataclasses

import numpy as np
import pytest

from repro.core.plandiff import (PoolSpec, REBATCH, decode_pool_key,
                                 diff_plans, plan_pools, pool_range)


# ------------------------------------------------------------- plan layer

@pytest.fixture(scope="module")
def smoke():
    from repro.serving.smoke import smoke_setup
    return smoke_setup("qwen3-1.7b", seed=0)


def _units(cfg):
    from repro.models import n_fragment_units
    return n_fragment_units(cfg)


def _frags(cfg, n=2):
    from repro.serving.smoke import smoke_fragments
    return smoke_fragments(cfg, n, rate=30.0, seed=0)


def test_with_disagg_splits_roles(smoke):
    from repro.serving.smoke import decode_plan, disagg_plan
    cfg, book, _ = smoke
    L = _units(cfg)
    base = plan_pools(decode_plan(cfg, book, _frags(cfg)))
    split = plan_pools(disagg_plan(cfg, book, _frags(cfg)))
    full = (cfg.name, 0, L)
    dkey = decode_pool_key(cfg.name, 0, L)
    assert base[full].role == "both" and dkey not in base
    # the full-range pool is re-roled, the decode pool rides along
    assert split[full].role == "prefill"
    assert split[dkey].role == "decode"
    assert pool_range(dkey) == full
    assert len(split) == len(base) + 1


def test_role_flip_is_rebatch_not_teardown(smoke):
    """Disaggregation rollout must keep the warm full-range pool: its
    key is unchanged, so the diff re-configures it in place (REBATCH)
    and only the decode pool is an add."""
    from repro.serving.smoke import decode_plan, disagg_plan
    cfg, book, _ = smoke
    diff = diff_plans(decode_plan(cfg, book, _frags(cfg)),
                      disagg_plan(cfg, book, _frags(cfg)))
    s = diff.summary()
    assert s["remove"] == 0
    assert s["add"] == 1                       # the decode pool
    flips = [a for a in diff.by_kind(REBATCH)
             if a.old.role != a.new.role]
    assert len(flips) == 1 and flips[0].new.role == "prefill"


def test_extra_pool_key_collision_raises(smoke):
    from repro.serving.smoke import decode_plan
    cfg, book, _ = smoke
    plan = decode_plan(cfg, book, _frags(cfg))
    full = (cfg.name, 0, _units(cfg))
    clash = PoolSpec(key=full, share=50, batch=2, n_instances=1)
    bad = dataclasses.replace(plan, meta={"extra_pools": (clash,)})
    with pytest.raises(ValueError, match="collides"):
        plan_pools(bad)


def test_pool_spec_rejects_unknown_role():
    with pytest.raises(ValueError, match="unknown pool role"):
        PoolSpec(key=("m", 0, 2), share=50, batch=1, n_instances=1,
                 role="prefetch")


def test_disagg_plan_requires_opt_in(smoke):
    """Deploying role-split pools without ``decode_disagg=True`` must
    fail loudly at deploy time, not strand traffic at runtime."""
    from repro.serving.executor import GraftExecutor
    from repro.serving.smoke import disagg_plan
    from repro.serving.transport import InProcessTransport
    cfg, book, params = smoke
    plan = disagg_plan(cfg, book, _frags(cfg))
    with pytest.raises(ValueError, match="decode_disagg"):
        GraftExecutor(plan, params, cfg, transport=InProcessTransport(),
                      decode_ctx=32, kv_block_tokens=4)


# ----------------------------------------------------- cross-arena handoff

def _make_kv(n_blocks=16, bt=4):
    from repro.serving.kvcache import PagedKVCache
    return PagedKVCache(n_blocks, bt, n_layers=1, n_kv_heads=1, head_dim=2)


def _fake_kv(n, base=0.0):
    k = (base + np.arange(n * 2, dtype=np.float32)).reshape(n, 1, 1, 2)
    return k, k + 0.5


SIG = ("m", 0, 7)


def _prefill(kv, rid, toks, base=0.0):
    n_shared = kv.begin(rid, SIG, toks)
    ks, vs = _fake_kv(len(toks), base)
    # per-token rows (n, L, KV, hd) -> the B=1 cache layout (L, 1, n, ...)
    kv.write_prompt_kv(rid, ks.transpose(1, 0, 2, 3)[:, None],
                       vs.transpose(1, 0, 2, 3)[:, None])
    return n_shared


def test_export_import_roundtrip_preserves_chain():
    src, dst = _make_kv(), _make_kv()
    toks = list(range(8))                       # two full blocks
    _prefill(src, 1, toks)
    payload = src.export_prefix(1)
    src.finish(1, retain=True)
    assert len(payload["blocks"]) == 2
    assert payload["sig"] == SIG and payload["block_tokens"] == 4

    r = dst.import_prefix(SIG, payload["blocks"])
    assert r == {"imported": 2, "reused": 0, "tokens_in": 8}
    # the importer's arena now holds byte-identical KV under the SAME
    # chain keys: a begin() on the importer shares the whole prompt
    assert dst.begin(2, SIG, toks) == 8
    ks, _vs = _fake_kv(8)
    got = np.concatenate([dst._k[b.idx, :b.filled]
                          for b in dst._seqs[2].blocks])
    np.testing.assert_array_equal(got, ks)
    dst.release(2)
    # re-importing the same prompt is a pure index hit
    r2 = dst.import_prefix(SIG, payload["blocks"])
    assert r2 == {"imported": 0, "reused": 2, "tokens_in": 0}
    assert dst.counters["handoff_blocks_in"] == 2
    assert dst.counters["handoff_reused"] == 2


def test_imported_partial_block_cows_on_append():
    """COW refcounts stay intact across the hop: appending to a shared
    imported partial block copies it first, leaving the retained block
    (and any other sharer) untouched."""
    src, dst = _make_kv(), _make_kv()
    toks = list(range(6))                       # one full + one partial
    _prefill(src, 1, toks)
    payload = src.export_prefix(1)
    src.finish(1, retain=True)
    assert dst.import_prefix(SIG, payload["blocks"])["imported"] == 2

    assert dst.begin(2, SIG, toks) == 6         # fully shared
    shared_last = dst._seqs[2].blocks[-1]
    before = dst._k[shared_last.idx].copy()
    assert dst.append([2], [99]) == []
    assert dst.counters["cow_copies"] == 1
    assert dst._seqs[2].blocks[-1].idx != shared_last.idx
    np.testing.assert_array_equal(dst._k[shared_last.idx], before)
    dst.release(2)
    assert dst.stats()["active_seqs"] == 0


def test_import_stops_cleanly_on_oom():
    """Chain keys need contiguity: a partial import keeps a clean prefix
    (later begin() recomputes the tail — degraded, never wrong)."""
    from repro.serving.kvcache import prompt_chain_keys
    src = _make_kv()
    dst = _make_kv(n_blocks=2)
    toks = list(range(12))                      # three blocks
    _prefill(src, 1, toks)
    payload = src.export_prefix(1)
    src.finish(1, retain=True)
    r = dst.import_prefix(SIG, payload["blocks"])
    assert r["imported"] == 2 and r["tokens_in"] == 8
    # exactly the chain PREFIX landed — the third chunk did not evict
    # its own parents to squeeze in
    keys = prompt_chain_keys(SIG, tuple(toks), 4)
    assert keys[0] in dst._index and keys[1] in dst._index
    assert keys[2] not in dst._index


def test_kv_frame_validates_on_decode():
    from repro.serving.kvcache import prompt_chain_keys
    from repro.serving.transport import (FrameError, decode_kv_blocks,
                                         encode_kv_blocks, is_kv_frame,
                                         kv_frame_nbytes)
    src = _make_kv()
    toks = list(range(8))
    _prefill(src, 1, toks)
    payload = src.export_prefix(1)
    src.finish(1, retain=True)
    frame = encode_kv_blocks(payload)
    assert is_kv_frame(frame) and kv_frame_nbytes(frame) > 0
    dec = decode_kv_blocks(frame)
    assert tuple(dec["sig"]) == SIG
    # decoded blocks re-import under identical chain keys
    keys = prompt_chain_keys(SIG, tuple(toks), 4)
    dst = _make_kv()
    dst.import_prefix(dec["sig"], dec["blocks"])
    assert all(k in dst._index for k in keys)

    bad = dict(frame)
    bad["blocks"] = [dict(frame["blocks"][0], filled=99)]
    with pytest.raises(FrameError):
        decode_kv_blocks(bad)


def test_executor_handoff_from_bf16_device_arena(smoke):
    """Through the executor at bfloat16: a prefill pool's export copies
    its device arena's blocks down and widens them to float32 (exactly
    the bf16 rows); a second pool imports them, admits the prompt fully
    shared, and decodes the same greedy tokens as a plain admission."""
    import jax

    from repro import models as M
    from repro.serving.executor import FragmentInstance
    from repro.serving.smoke import decode_plan
    from repro.serving.transport import decode_kv_blocks
    cfg, book, _ = smoke
    spec = next(iter(plan_pools(decode_plan(cfg, book, _frags(cfg)))
                     .values()))
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)

    def pool():
        return FragmentInstance(params, cfg, spec, decode_ctx=32,
                                kv_blocks=32, kv_block_tokens=4)

    def run(inst, handoff=None):
        r = inst.decode_admit(1, "c0", toks, 6, sig=SIG, handoff=handoff)
        assert r["admitted"]
        # the prompt's K and V rows in the dense cache, (n, L, KV, hd)
        r["rows"] = [np.asarray(inst._dc[x][:, 0, :len(toks)]).transpose(
            1, 0, 2, 3) for x in "kv"]
        out = [r["tok"]]
        while inst.decode_active:
            out += [ev["tok"] for ev in inst.decode_step_batch()["events"]]
        return r, out

    toks = np.random.RandomState(12).randint(0, cfg.vocab_size, 10).astype(
        np.int32)
    plain, want = run(pool())

    src = pool()
    exp = src.prefill_export(1, "c0", toks, SIG)
    assert exp["exported"] and exp["tok"] == want[0]
    assert src.kv.dtype == np.dtype("bfloat16")
    handoff = decode_kv_blocks(exp["kv"])
    k = np.concatenate([b["k"] for b in handoff["blocks"]])
    v = np.concatenate([b["v"] for b in handoff["blocks"]])
    assert k.dtype == v.dtype == np.float32 and k.shape[0] == len(toks)
    # the cold prefill's cache row is the plain admission's: its bf16 rows
    assert plain["rows"][0].dtype == np.dtype("bfloat16")
    for got, rows in zip((k, v), plain["rows"]):
        np.testing.assert_array_equal(got, rows.astype(np.float32))
    # KV left the device once, at bf16; the token once more
    assert src.d2h_bytes == 4 + 2 * src.kv.max_seq_tokens * np.prod(
        k.shape[1:]) * 2

    dst = pool()
    r, got = run(dst, handoff)
    assert r["n_shared"] == len(toks)
    assert dst.kv.counters["handoff_blocks_in"] == len(handoff["blocks"])
    assert got == want


# -------------------------------------------------------------- serving

def _serve_decode(server, cfg, frags, prompts, *, max_new=4,
                  budget_ms=5000.0):
    from repro.serving.executor import ServeRequest
    served = []
    for i, toks in enumerate(prompts):
        f = frags[i % len(frags)]
        req = ServeRequest(client=f.client, tokens=toks,
                           max_new_tokens=max_new,
                           tpot_budget_ms=2000.0)
        server.submit(req, 0, budget_ms)
        served.append((req, max_new))
    assert server.join(timeout=600.0), "decode run never drained"
    return served


@pytest.mark.slow
def test_disagg_serving_token_exact_and_shares_across_hop(smoke):
    """The tentpole, end to end: a prefill-role and a decode-role pool
    over the same range. Every stream must match the single-pool
    continuous path token-for-token (and the unbatched reference), at
    least one KV handoff must cross the transport, and a repeated
    prompt's second handoff must find its blocks already resident on
    the decode arena (sharing survives the hop)."""
    from repro.serving.executor import GraftExecutor
    from repro.serving.server import GraftServer
    from repro.serving.smoke import (check_decode_against_reference,
                                     decode_plan, disagg_plan)
    from repro.serving.transport import InProcessTransport
    cfg, book, params = smoke
    frags = _frags(cfg)
    rng = np.random.RandomState(7)
    uniq = [rng.randint(0, cfg.vocab_size, 12).astype(np.int32)
            for _ in range(3)]
    prompts = uniq + [uniq[0].copy()]           # one repeat -> reuse

    outs = {}
    for mode in ("single", "disagg"):
        if mode == "single":
            plan = decode_plan(cfg, book, frags, batch=4)
            ex = GraftExecutor(plan, params, cfg,
                               transport=InProcessTransport(),
                               decode_ctx=64, kv_block_tokens=4)
        else:
            plan = disagg_plan(cfg, book, frags, batch=4)
            ex = GraftExecutor(plan, params, cfg,
                               transport=InProcessTransport(),
                               decode_ctx=64, kv_block_tokens=4,
                               decode_disagg=True)
        server = GraftServer(ex, book=book).start()
        try:
            served = _serve_decode(server, cfg, frags, prompts)
            outs[mode] = [list(r.out_tokens or []) for r, _ in served]
            if mode == "disagg":
                rep = server.report()
                stats = {s["role"]: s for s in ex.pool_stats().values()}
            check_decode_against_reference(cfg, params, served)
        finally:
            server.stop(drain=False, timeout=10.0)
            ex.close()
    assert outs["single"] == outs["disagg"]     # path-for-path exact
    assert rep["kv_handoffs"] >= 1 and rep["kv_handoff_ms"] > 0.0
    assert rep["decode_local"] == 0
    assert stats["prefill"]["prefill_exports"] >= len(prompts)
    assert stats["prefill"]["decode_active"] == 0      # never resident
    dkv = stats["decode"]["kv"]
    assert stats["decode"]["kv_handoffs_in"] >= 1
    assert dkv["handoff_blocks_in"] >= 1
    # the repeated prompt's blocks were already resident on the decode
    # arena (imported chain keys index-hit) — sharing survived the hop
    assert dkv["handoff_reused"] + dkv["prefix_hits"] >= 1
    assert dkv["active_seqs"] == 0                     # all drained


@pytest.mark.slow
def test_dead_prefill_pool_degrades_not_strands(smoke):
    """Kill the channel to the prefill pool mid-run: the two-phase admit
    observes the typed connection error, drops the handoff, and the
    decode pool self-prefills — token-exact, nothing stranded, and the
    handoff counter stops growing."""
    from repro.serving.executor import GraftExecutor
    from repro.serving.server import GraftServer
    from repro.serving.smoke import (check_decode_against_reference,
                                     disagg_plan)
    from repro.serving.transport import InProcessTransport
    from test_faults import FlakyTransport
    cfg, book, params = smoke
    frags = _frags(cfg)
    L = _units(cfg)
    tp = FlakyTransport(InProcessTransport())
    ex = GraftExecutor(disagg_plan(cfg, book, frags, batch=4), params,
                       cfg, transport=tp, decode_ctx=64,
                       kv_block_tokens=4, decode_disagg=True)
    server = GraftServer(ex, book=book).start()
    rng = np.random.RandomState(11)
    try:
        warm = _serve_decode(server, cfg, frags,
                             [rng.randint(0, cfg.vocab_size,
                                          12).astype(np.int32)])
        rep = server.report()
        assert rep["kv_handoffs"] >= 1
        handoffs_before = rep["kv_handoffs"]

        pkey = (cfg.name, 0, L)
        server._pool_handle(pkey).channel.broken = True
        server._residency_cache.clear()
        cut = _serve_decode(server, cfg, frags,
                            [rng.randint(0, cfg.vocab_size,
                                         12).astype(np.int32)
                             for _ in range(2)])
        rep = server.report()
        check_decode_against_reference(cfg, params, warm + cut)
        assert rep["kv_handoffs"] == handoffs_before    # no fake handoffs
        assert rep["decode_served"] == len(warm) + len(cut)

        server._pool_handle(pkey).channel.broken = False
        healed = _serve_decode(server, cfg, frags,
                               [rng.randint(0, cfg.vocab_size,
                                            12).astype(np.int32)])
        check_decode_against_reference(cfg, params, healed)
        assert server.report()["kv_handoffs"] > handoffs_before
    finally:
        server.stop(drain=False, timeout=10.0)
        ex.close()


def test_orphaned_decode_pool_removal_refused(smoke):
    """A replan that removes the prefill feeder while its decode pool
    survives must be refused — the decode pool would strand."""
    from repro.serving.executor import GraftExecutor
    from repro.serving.smoke import disagg_plan, mixed_depth_plan
    from repro.serving.transport import InProcessTransport
    cfg, book, params = smoke
    frags = _frags(cfg)
    L = _units(cfg)
    ex = GraftExecutor(disagg_plan(cfg, book, frags, batch=4), params,
                       cfg, transport=InProcessTransport(),
                       decode_ctx=32, kv_block_tokens=4,
                       decode_disagg=True)
    try:
        # new plan: stage pools move to [1, L) but the decode pool
        # over [0, L) rides along -> its feeder would vanish
        moved = mixed_depth_plan(
            cfg, book, [dataclasses.replace(f, p=1) for f in frags], s=1)
        dspec = PoolSpec(key=decode_pool_key(cfg.name, 0, L), share=50,
                         batch=4, n_instances=1, role="decode")
        bad = dataclasses.replace(moved,
                                  meta={"extra_pools": (dspec,)})
        with pytest.raises(RuntimeError, match="no prefill feeder"):
            ex.apply_plan(bad)
    finally:
        ex.close()


# ------------------------------------------------------------ controller

def test_disagg_pressure_trigger_arms_and_disarms():
    from repro.core.profiles import ProfileBook
    from repro.serving.controller import ServingController
    c = ServingController(ProfileBook(), planner=object(),
                          disagg_pressure_frac=0.25, window_ms=1000.0)
    assert "disagg_pressure" not in c._triggers({}, 0.0)
    c.observe_disagg_pressure(100.0, 0.1)       # below threshold
    assert "disagg_pressure" not in c._triggers({}, 200.0)
    c.observe_disagg_pressure(300.0, 0.6)
    assert "disagg_pressure" in c._triggers({}, 400.0)
    # stale pressure disarms instead of re-firing forever
    assert "disagg_pressure" not in c._triggers({}, 2000.0)
    assert c._disagg_pressure is None


def test_server_feeds_disagg_pressure_deltas(smoke):
    """The server reports the per-tick LOCAL fraction of decode
    completions, not a lifetime average."""
    from repro.serving.executor import GraftExecutor
    from repro.serving.server import GraftServer
    from repro.serving.smoke import decode_plan
    from repro.serving.transport import InProcessTransport

    class Probe:
        def __init__(self):
            self.fracs = []

        def observe_disagg_pressure(self, now_ms, frac):
            self.fracs.append(frac)

    cfg, book, params = smoke
    ex = GraftExecutor(decode_plan(cfg, book, _frags(cfg)), params, cfg,
                       transport=InProcessTransport(), decode_ctx=32,
                       kv_block_tokens=4)
    # never started: the feed is exercised directly, without the timer
    # thread racing the marks
    server = GraftServer(ex, book=book)
    probe = Probe()
    try:
        server.controller = probe
        server.stats["decode_local"] = 3
        server.stats["decode_served"] = 4
        server._feed_disagg_pressure()
        assert probe.fracs == [0.75]
        server._feed_disagg_pressure()          # no new completions
        assert probe.fracs == [0.75]
        server.stats["decode_served"] = 8       # 4 new, all pool-served
        server._feed_disagg_pressure()
        assert probe.fracs == [0.75, 0.0]
    finally:
        ex.close()
