"""Decode serving: paged KV-cache invariants, iteration-level admission,
and the continuous-batching server path staying numerically exact.

The cache tests drive the arena with small float32 arrays and read it
back to the host; the executor/server tests run the real decode path at
smoke scale against the unbatched reference decoder — mid-decode
admission must not perturb any resident stream's tokens.
"""
import numpy as np
import pytest

from repro.serving.batcher import BatchItem, MicroBatcher, ShedPolicy
from repro.serving.kvcache import (KVCacheOOM, PagedKVCache,
                                   prompt_chain_keys)

SIG = ("m", 0, 7)


def make_kv(n_blocks=8, bt=4):
    return PagedKVCache(n_blocks, bt, n_layers=1, n_kv_heads=1, head_dim=2)


def fake_kv(n, base=0.0):
    """(n, L, KV, hd) distinguishable per-token KV payloads."""
    ks = np.arange(n, dtype=np.float32).reshape(n, 1, 1, 1) + base
    return np.broadcast_to(ks, (n, 1, 1, 2)).copy()


def write(kv, rid, ks, vs):
    """Write per-token rows (n, L, KV, hd) of the prompt from position 0
    through the B=1 cache layout (L, 1, n, KV, hd) the arena takes."""
    kv.write_prompt_kv(rid, ks.transpose(1, 0, 2, 3)[:, None],
                       vs.transpose(1, 0, 2, 3)[:, None])


def append(kv, rid, token):
    """Book one token for ``rid`` as a one-stream decode step."""
    return kv.append([rid], [token])


def gather(kv, rid, n=None):
    """The arena's K and V of ``rid``'s first ``n`` prompt tokens
    (default: the whole prompt) as host rows (n, L, KV, hd)."""
    n = kv._seqs[rid].prompt_len if n is None else n
    return tuple(np.asarray(x)[:, 0, :n].transpose(1, 0, 2, 3)
                 for x in kv.gather(rid, n))


# ---------------------------------------------------------------- kv cache

def test_begin_write_gather_roundtrip():
    kv = make_kv()
    toks = list(range(6))
    assert kv.begin(1, SIG, toks) == 0
    ks = fake_kv(6)
    write(kv, 1, ks, ks * 10)
    k, v = gather(kv, 1)
    np.testing.assert_array_equal(k, ks)
    np.testing.assert_array_equal(v, ks * 10)
    # a decode token is booked, not stored: the prompt still reads back
    # and a gather past it raises
    assert append(kv, 1, 99) == []
    assert kv.n_resident(1) == 7
    k, _ = gather(kv, 1)
    np.testing.assert_array_equal(k, ks)
    with pytest.raises(ValueError, match="prompt"):
        kv.gather(1, 7)


def test_double_free_raises():
    kv = make_kv()
    kv.begin(1, SIG, [1, 2, 3])
    blk = kv._seqs[1].blocks[0]
    kv.release(1)
    assert blk.free
    with pytest.raises(RuntimeError, match="double free"):
        kv._free_block(blk)


def test_release_returns_blocks_to_free_list():
    kv = make_kv(n_blocks=4, bt=4)
    free0 = kv.n_free
    kv.begin(1, SIG, list(range(10)))           # 3 blocks
    assert kv.n_free == free0 - 3
    kv.release(1)
    assert kv.n_free == free0
    assert kv.stats()["frees"] == 3


def test_oom_when_all_blocks_active_and_unwind():
    kv = make_kv(n_blocks=2, bt=4)
    kv.begin(1, SIG, list(range(8)))            # both blocks held, ref 1
    free0 = kv.n_free
    with pytest.raises(KVCacheOOM):
        kv.begin(2, SIG, list(range(100, 105)))
    # the partially-admitted sequence must roll back completely
    assert kv.n_free == free0
    assert 2 not in kv._seqs
    assert kv.stats()["oom"] == 1


def test_prefix_share_refcounts_full_blocks():
    kv = make_kv(n_blocks=8, bt=4)
    toks = list(range(8))                        # 2 full blocks
    kv.begin(1, SIG, toks)
    ks = fake_kv(8)
    write(kv, 1, ks, ks)
    kv.finish(1, retain=True)                    # indexed, ref 0, resident
    assert kv.stats()["frees"] == 0
    shared = kv.begin(2, SIG, toks)
    assert shared == 8
    assert kv.stats()["prefix_hits"] == 1
    assert kv.stats()["prefix_tokens_reused"] == 8
    assert all(b.ref == 1 for b in kv._seqs[2].blocks)
    # the sharer's gather sees the donor's KV without any write
    k, _ = gather(kv, 2)
    np.testing.assert_array_equal(k, ks)
    # a different-sig request must NOT match the same tokens
    assert kv.begin(3, ("m", 0, 99), toks) == 0


def test_partial_block_shares_only_exact_tail():
    kv = make_kv(n_blocks=8, bt=4)
    kv.begin(1, SIG, list(range(6)))             # 1 full + 1 partial
    ks = fake_kv(6)
    write(kv, 1, ks, ks)
    kv.finish(1, retain=True)
    # same full-block prefix but different tail: only the full block hits
    assert kv.begin(2, SIG, [0, 1, 2, 3, 9, 9]) == 4
    kv.release(2)
    # identical prompt: both blocks hit
    assert kv.begin(3, SIG, list(range(6))) == 6


def test_cow_on_shared_partial_block():
    kv = make_kv(n_blocks=8, bt=4)
    toks = list(range(6))
    kv.begin(1, SIG, toks)
    ks = fake_kv(6)
    write(kv, 1, ks, ks)
    kv.finish(1, retain=True)                    # partial tail indexed "P"
    kv.begin(2, SIG, toks)                       # shares both blocks
    donor_tail = kv._seqs[2].blocks[-1]
    # appending into the shared partial block must copy it first
    append(kv, 2, 77)
    assert kv.counters["cow_copies"] == 1
    assert kv._seqs[2].blocks[-1] is not donor_tail
    # the donor's indexed block is untouched: a third request still
    # shares the full 6-token prefix, and its KV is the original
    assert kv.begin(3, SIG, toks) == 6
    k3, _ = gather(kv, 3, 6)
    np.testing.assert_array_equal(k3, ks)
    # ...and the COW'd private copy holds the same six prompt positions
    k2, v2 = gather(kv, 2)
    np.testing.assert_array_equal(k2, ks)
    np.testing.assert_array_equal(v2, ks)


def test_lru_eviction_reclaims_retained_blocks():
    kv = make_kv(n_blocks=2, bt=4)
    kv.begin(1, SIG, list(range(8)))
    write(kv, 1, fake_kv(8), fake_kv(8))
    kv.finish(1, retain=True)                    # both blocks retained
    assert kv.n_free == 0
    # allocation pressure evicts the retained blocks instead of OOMing
    kv.begin(2, SIG, [50, 51, 52, 53, 54])       # needs 2 blocks
    assert kv.stats()["evictions"] == 2
    kv.release(2)
    # the evicted prefix is gone from the index
    assert kv.begin(3, SIG, list(range(8))) == 0


def test_cow_and_eviction_interplay():
    """A COW'd block must be a PRIVATE copy: evicting the donor's index
    entry later cannot affect the sharer's data."""
    kv = make_kv(n_blocks=4, bt=4)
    toks = list(range(6))
    kv.begin(1, SIG, toks)
    ks = fake_kv(6)
    write(kv, 1, ks, ks)
    kv.finish(1, retain=True)
    kv.begin(2, SIG, toks)
    append(kv, 2, 7)                             # COW
    # pressure: evict every retained block (donor's index entries)
    kv.begin(3, ("m", 1, 0), list(range(200, 208)))
    assert kv.stats()["evictions"] > 0
    # the COW'd block still holds the six prompt positions
    k2, v2 = gather(kv, 2)
    np.testing.assert_array_equal(k2, ks)
    np.testing.assert_array_equal(v2, ks)


def test_util_frac_and_has_room():
    kv = make_kv(n_blocks=4, bt=4)
    assert kv.util_frac() == 1.0                 # empty arena wastes nothing
    kv.begin(1, SIG, [1, 2])                     # 2 of 4 slots in 1 block
    assert kv.util_frac() == pytest.approx(0.5)
    assert kv.has_room(2, n_resident=2)          # fits the same block
    assert kv.has_room(12, n_resident=2)
    assert not kv.has_room(15, n_resident=2)     # needs 4 more blocks, has 3


def test_prompt_chain_keys_structure():
    keys = prompt_chain_keys(SIG, (1, 2, 3, 4, 5), 2)
    assert len(keys) == 3
    assert keys[0][0] == "B" and keys[-1][0] == "P"
    assert keys[0][1] == ("root", SIG)
    assert keys[1][1] == keys[0]                 # chained parents
    # same tokens under another sig produce disjoint keys
    assert prompt_chain_keys(("x",), (1, 2, 3, 4, 5), 2)[0] != keys[0]


# ----------------------------------------------------- batcher / shed policy

def test_take_pops_immediately_in_queue_order():
    b = MicroBatcher(max_batch=8)
    for rid, fl in [(0, 50.0), (1, 10.0), (2, 30.0)]:
        b.put(BatchItem(rid=rid, client="c", payload=rid,
                        flush_ms=fl, deadline_ms=1e9, decode=True))
    assert b.pop_ready(now_ms=0.0) == []         # close policy: not due
    got = b.take(2)                              # step boundary: immediate
    assert [it.rid for it in got] == [1, 2]      # earliest-queued first
    assert b.stats.taken == 2
    assert [it.rid for it in b.take(5)] == [0]
    assert len(b) == 0


def test_hopeless_decode_ttft_and_total():
    # TTFT side: first token can't land by its deadline
    assert ShedPolicy.hopeless_decode(100.0, 105.0, 10.0, 1e9, 1.0, 4)
    # total side: TTFT fine but 10 remaining tokens at 50ms/t blow the
    # absolute deadline
    assert ShedPolicy.hopeless_decode(100.0, 200.0, 10.0, 400.0, 50.0, 10)
    # both fine
    assert not ShedPolicy.hopeless_decode(100.0, 200.0, 10.0, 700.0,
                                          50.0, 10)


def test_should_shed_weighted_charge():
    pol = ShedPolicy(budget_frac=0.25, window=64)
    # no admitted history: a 5-token shed would be 100% shed rate
    assert not pol.should_shed("c", charge=5)
    pol.note_admitted("c", weight=20)
    # 5 of ~26 outcomes shed stays under 25%
    assert pol.should_shed("c", charge=5)
    # the charge was recorded: another 5 would cross the budget
    assert not pol.should_shed("c", charge=5)


# -------------------------------------------------- real decode execution

@pytest.fixture(scope="module")
def decode_pool():
    from repro.serving.executor import GraftExecutor
    from repro.serving.smoke import (decode_plan, smoke_fragments,
                                     smoke_setup)
    from repro.serving.transport import InProcessTransport
    cfg, book, params = smoke_setup(seq_len=8, seed=0)
    frags = smoke_fragments(cfg, 2, seed=0)
    plan = decode_plan(cfg, book, frags, batch=3)
    ex = GraftExecutor(plan, params, cfg, transport=InProcessTransport(),
                       decode_ctx=32, kv_blocks=32, kv_block_tokens=4)
    yield cfg, params, ex
    ex.close()


def drive_to_done(handle, want_rids):
    out, steps = {}, 0
    while len(out) < len(want_rids):
        rep = handle.decode_step()
        for ev in rep["events"]:
            if ev.get("done"):
                assert not ev.get("oom")
                out[ev["rid"]] = ev["tokens"]
        steps += 1
        assert steps < 64, "decode never finished"
    return out


def test_mid_decode_admission_preserves_numerics(decode_pool):
    """Admitting B into A's RUNNING decode batch must not change either
    stream's tokens vs decoding each alone."""
    from repro.serving.smoke import reference_decode
    cfg, params, ex = decode_pool
    key = next(iter(ex.pool_specs()))
    handle = ex.handle(key)
    rng = np.random.RandomState(3)
    tA = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    tB = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    refA = reference_decode(cfg, params, tA, 5)
    refB = reference_decode(cfg, params, tB, 5)

    rA = handle.decode_admit(101, "c0", tA, 5, sig=("s", 0, 0))
    assert rA["admitted"] and rA["tok"] == refA[0]
    outA = [rA["tok"]]
    for _ in range(2):                           # A mid-stream
        rep = handle.decode_step()
        assert rep["active"] == 1
    rB = handle.decode_admit(102, "c1", tB, 5, sig=("s", 0, 0))
    assert rB["admitted"] and rB["tok"] == refB[0]
    done = drive_to_done(handle, [101, 102])
    assert done[101] == refA
    assert done[102] == refB


def _solo_pool(decode_pool, decode_ctx=32):
    """A fresh decode instance of the fixture's one pool, driven
    directly, so its dense cache and arena can be read."""
    from repro.serving.executor import FragmentInstance
    cfg, params, ex = decode_pool
    spec = next(iter(ex.pool_specs().values()))
    return FragmentInstance(params, cfg, spec, decode_ctx=decode_ctx,
                            kv_blocks=32, kv_block_tokens=4)


@pytest.fixture(scope="module")
def bf16_pool(decode_pool):
    """The fixture's model and pool at bfloat16: the dtype the served
    models' caches hold, and so the arena's."""
    import dataclasses

    import jax

    from repro import models as M
    cfg, _, ex = decode_pool
    cfg = dataclasses.replace(cfg, dtype="bfloat16")
    params = jax.jit(M.init_params, static_argnums=1)(
        jax.random.PRNGKey(0), cfg)
    return cfg, params, ex


def _bits(x) -> np.ndarray:
    x = np.asarray(x)
    return x.view(f"u{x.dtype.itemsize}")


def _assert_arena_matches_dense(inst, rid):
    """The arena's K and V of stream ``rid``'s prompt are its dense
    cache row at the same positions, at the cache's own dtype, bit for
    bit. Returns the prompt's length."""
    slot = next(i for i, s in enumerate(inst._slots) if s and s["rid"] == rid)
    n = inst.kv._seqs[rid].prompt_len
    for got, dense in zip(inst.kv.gather(rid), (inst._dc["k"], inst._dc["v"])):
        assert got.dtype == dense.dtype == inst.kv.dtype
        np.testing.assert_array_equal(_bits(got[:, 0, :n]),
                                      _bits(dense[:, slot, :n]))
    return n


def _assert_no_decode_rows(inst, rid):
    """The arena stores no decode row: a gather past the prompt of
    stream ``rid``, which has decoded, raises."""
    assert inst.kv.n_resident(rid) > inst.kv._seqs[rid].prompt_len
    with pytest.raises(ValueError, match="prompt"):
        inst.kv.gather(rid, inst.kv.n_resident(rid))


def test_arena_holds_dense_cache_rows_exactly(bf16_pool):
    """Two cold admissions, the second mid-way through the first's
    decode: after several steps the bf16 arena still holds exactly what
    the dense cache holds at each prompt's positions, and nothing past
    them."""
    cfg = bf16_pool[0]
    inst = _solo_pool(bf16_pool)
    rng = np.random.RandomState(7)
    tA = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    tB = rng.randint(0, cfg.vocab_size, 6).astype(np.int32)
    assert inst.decode_admit(401, "c0", tA, 12, sig=("e", 0, 0))["admitted"]
    assert _assert_arena_matches_dense(inst, 401) == 8
    for _ in range(3):
        inst.decode_step_batch()
    assert inst.decode_admit(402, "c1", tB, 12, sig=("e", 0, 0))["admitted"]
    assert _assert_arena_matches_dense(inst, 402) == 6
    for _ in range(4):
        inst.decode_step_batch()
    assert inst._dc["k"].dtype == np.dtype("bfloat16")
    assert _assert_arena_matches_dense(inst, 401) == 8
    assert _assert_arena_matches_dense(inst, 402) == 6
    assert inst.kv.n_resident(401) == 8 + 7
    assert inst.kv.n_resident(402) == 6 + 4
    _assert_no_decode_rows(inst, 401)
    _assert_no_decode_rows(inst, 402)


@pytest.mark.parametrize("tail", [3, 0], ids=["private_tail", "cow_tail"])
def test_arena_exact_after_shared_prefix_admission(bf16_pool, tail):
    """A prompt whose first blocks are already in the arena is seeded
    from them on the device and steps the rest through the B=1 step
    loop; the arena still holds exactly the dense cache's row at the
    prompt's positions. With no tail the whole prompt is shared, its last
    block a donor's partial block, which the first append copies (COW)
    before booking into it."""
    cfg = bf16_pool[0]
    inst = _solo_pool(bf16_pool)
    rng = np.random.RandomState(8)
    head = rng.randint(0, cfg.vocab_size, 8 if tail else 6).astype(np.int32)
    toks = np.concatenate([head, rng.randint(0, cfg.vocab_size, tail)
                           .astype(np.int32)])
    r0 = inst.decode_admit(501, "c0", head, 1, sig=("f", 0, 0))
    assert r0["admitted"] and r0["done"]          # retained in the arena
    r1 = inst.decode_admit(502, "c0", toks, 6, sig=("f", 0, 0))
    assert r1["admitted"] and r1["n_shared"] == len(head)
    _assert_arena_matches_dense(inst, 502)
    for _ in range(3):
        inst.decode_step_batch()
    assert inst.kv.counters["cow_copies"] == (0 if tail else 1)
    assert _assert_arena_matches_dense(inst, 502) == len(toks)
    assert inst.kv.n_resident(502) == len(toks) + 3
    _assert_no_decode_rows(inst, 502)


def test_arena_programs_do_not_grow_with_prompt_length(bf16_pool):
    """Admissions of three prompt lengths, cold and prefix-shared, add
    no compiled arena program after the first of each kind: block
    tables and positions are traced, never shapes."""
    from repro.serving import executor, kvcache
    fns = (kvcache._write_blocks, kvcache._gather_blocks,
           executor._seed_prefix)
    cfg = bf16_pool[0]
    inst = _solo_pool(bf16_pool)
    rng = np.random.RandomState(10)
    sizes = None
    for i, n in enumerate((5, 9, 14)):
        toks = rng.randint(0, cfg.vocab_size, n).astype(np.int32)
        assert inst.decode_admit(700 + i, "c0", toks, 1,
                                 sig=("h", 0, 0))["done"]   # retained
        # the same prompt and one more token: shares the full blocks
        assert inst.decode_admit(710 + i, "c0", np.append(toks, 1), 2,
                                 sig=("h", 0, 0))["n_shared"] > 0
        inst.decode_step_batch()
        now = [f._cache_size() for f in fns]
        assert sizes is None or now == sizes, (n, sizes, now)
        sizes = now


def test_arena_append_donates_the_arena(bf16_pool):
    """An admission's prompt write updates the arena in place: the
    arena's previous buffers are deleted, so no second arena is held. A
    decode step then leaves the arena's buffers untouched: it writes no
    row there."""
    import jax
    import jax.numpy as jnp
    x = jnp.zeros(4)
    jax.jit(lambda a: a + 1, donate_argnums=0)(x)
    if not x.is_deleted():
        pytest.skip("this backend does not donate buffers")
    cfg = bf16_pool[0]
    inst = _solo_pool(bf16_pool)
    inst._ensure_decode()
    k0, v0 = inst.kv._k, inst.kv._v
    toks = np.random.RandomState(11).randint(0, cfg.vocab_size, 7)
    assert inst.decode_admit(801, "c0", toks.astype(np.int32), 4,
                             sig=("i", 0, 0))["admitted"]
    assert k0.is_deleted() and v0.is_deleted()
    k0, v0 = inst.kv._k, inst.kv._v
    inst.decode_step_batch()
    assert inst.kv._k is k0 and inst.kv._v is v0
    assert not k0.is_deleted()


@pytest.mark.parametrize("which", ["float32", "bfloat16"])
def test_step_reads_written_rows_not_the_cache(decode_pool, bf16_pool,
                                                which):
    """A step copies only its tokens to the host, and an admission only
    its first token, whatever the cache's length or dtype: the K and V
    rows stay on the device."""
    pool = bf16_pool if which == "bfloat16" else decode_pool
    cfg = pool[0]
    rng = np.random.RandomState(9)
    toks = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    for ctx in (32, 64):
        inst = _solo_pool(pool, decode_ctx=ctx)
        assert inst.decode_admit(601, "c0", toks, 4, sig=("g", 0, 0))[
            "admitted"]
        assert inst.d2h_bytes == 4
        for _ in range(2):
            inst.decode_step_batch()
        B = len(inst._slots)
        assert inst.d2h_bytes == 4 + 2 * B * 4


def test_decode_abort_frees_slot_and_blocks(decode_pool):
    cfg, params, ex = decode_pool
    key = next(iter(ex.pool_specs()))
    handle = ex.handle(key)
    rng = np.random.RandomState(4)
    toks = rng.randint(0, cfg.vocab_size, 8).astype(np.int32)
    r = handle.decode_admit(201, "c0", toks, 16, sig=("a", 0, 0))
    assert r["admitted"]
    s0 = handle.stats()
    assert s0["decode_active"] == 1
    assert handle.decode_abort(201)
    s1 = handle.stats()
    assert s1["decode_active"] == 0
    assert s1["kv"]["active_seqs"] == 0
    assert not handle.decode_abort(201)                # idempotent


def test_ctx_overflow_refused(decode_pool):
    cfg, params, ex = decode_pool
    key = next(iter(ex.pool_specs()))
    handle = ex.handle(key)
    toks = np.zeros(8, np.int32)
    r = handle.decode_admit(301, "c0", toks, 99, sig=("b", 0, 0))
    assert not r["admitted"] and r["reason"] == "ctx_overflow"


@pytest.mark.slow
def test_decode_server_smoke_end_to_end():
    """Full server path: continuous batching + paged KV + TTFT/TPOT
    records, every stream checked against the unbatched reference."""
    from repro.serving.smoke import run_decode_smoke
    rep = run_decode_smoke(n_requests=8, n_clients=2, max_new=4,
                           seq_len=8, seed=1)
    assert rep["numerics_ok"], rep.get("numerics_error")
    assert rep["decode_served"] + rep["decode_local"] == 8
    assert rep["decode"]["n"] == 8
    assert rep["decode"]["tokens"] == 32
    assert rep["decode"]["ttft_p50_ms"] > 0
    assert rep["kv"].get("oom", 0) == 0
