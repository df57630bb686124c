"""Ragged (sequence-packed) fragment execution and its satellite fixes:
per-request extras stacking, honest compile counting, phantom-uplink
skipping, and the bucket/packing layout policies."""

import jax
import numpy as np
import pytest

from repro import models as M
from repro.configs import get_smoke_config
from repro.core.plandiff import PoolSpec
from repro.models.packed import (is_packable, pack_segments,
                                 packed_fragment_fn, run_fragment_packed)
from repro.serving import ServeRequest
from repro.serving.batcher import bucket_size, seq_bucket, token_bucket
from repro.serving.executor import FragmentInstance, PoolHandle

ATOL, RTOL = 5e-5, 1e-3


@pytest.fixture(scope="module")
def dense():
    cfg = get_smoke_config("qwen3-1.7b")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _spec(cfg, start, end, batch=4):
    return PoolSpec(key=(cfg.name, start, end), share=100, batch=batch,
                    n_instances=1)


def _tokens(rng, cfg, n):
    return rng.randint(0, cfg.vocab_size, n).astype(np.int32)


# ------------------------------------------------------------ packed layout

def test_pack_segments_layout():
    seg, pos, cu = pack_segments([3, 5], 16)
    assert list(cu) == [0, 3, 8]
    assert list(seg[:8]) == [0, 0, 0, 1, 1, 1, 1, 1]
    assert (seg[8:] == 2).all()                   # pad = own segment id
    assert list(pos[:8]) == [0, 1, 2, 0, 1, 2, 3, 4]
    assert list(pos[8:]) == list(range(8))        # pad positions restart too


def test_pack_segments_rejects_overflow():
    with pytest.raises(ValueError):
        pack_segments([9, 9], 16)


def test_is_packable_policy():
    dense = get_smoke_config("qwen3-1.7b")
    assert is_packable(dense)
    assert not is_packable(dense, extras={"images": np.zeros((1, 2, 4))})
    vlm = get_smoke_config("llama-3.2-vision-90b")
    assert not is_packable(vlm)
    moe = get_smoke_config("olmoe-1b-7b")
    # grouped dispatch sizes expert capacity from the TOTAL token count,
    # so packing would change routing; only the dense dispatch packs
    assert is_packable(moe) == (moe.moe_impl == "dense")


# ------------------------------------------------- packed == per-request

def test_packed_equals_per_request_full_range(dense):
    cfg, params = dense
    rng = np.random.RandomState(1)
    L = M.n_fragment_units(cfg)
    payloads = [_tokens(rng, cfg, n) for n in (5, 9, 3)]
    packed = run_fragment_packed(params, cfg, payloads, 0, L,
                                 pad_to=token_bucket(17))
    for p, y in zip(payloads, packed):
        want = M.run_fragment(params, cfg, np.asarray(p)[None], 0, L)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]),
                                   atol=ATOL, rtol=RTOL)


def test_packed_equals_per_request_mid_fragment(dense):
    cfg, params = dense
    rng = np.random.RandomState(2)
    L = M.n_fragment_units(cfg)
    payloads = [rng.randn(n, cfg.d_model).astype(np.float32) * 0.1
                for n in (4, 7)]
    packed = run_fragment_packed(params, cfg, payloads, 1, L)
    for p, y in zip(payloads, packed):
        want = M.run_fragment(params, cfg, p[None], 1, L)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]),
                                   atol=ATOL, rtol=RTOL)


def test_packed_program_shared_across_offsets(dense):
    """Equal-depth fragments at different offsets hit ONE compiled
    program — the compile-collapse the replan path depends on."""
    cfg, _ = dense
    L = M.n_fragment_units(cfg)
    if L < 2:
        pytest.skip("needs >= 2 fragment units")
    assert packed_fragment_fn(cfg, 1, False, False) is \
        packed_fragment_fn(cfg, 1, False, False)
    a = packed_fragment_fn(cfg, 1, False, True)
    b = packed_fragment_fn(cfg, 1, False, False)
    assert a is not b                      # boundary flags key the program


# ------------------------------------------------------ executor-level

def test_executor_packed_mixed_lengths_across_replan():
    """Mixed-length packed serving == monolithic forward, and stays so
    across a mid-run apply_plan that moves the alignment boundary."""
    from repro.core import Fragment
    from repro.serving import GraftExecutor
    from repro.serving.smoke import mixed_depth_plan, smoke_setup

    cfg, book, params = smoke_setup("qwen3-1.7b", n_layers=4)
    frags = [Fragment(cfg.name, 0, 60.0, 30.0, client="c0"),
             Fragment(cfg.name, 1, 45.0, 30.0, client="c1"),
             Fragment(cfg.name, 1, 70.0, 30.0, client="c2")]
    rng = np.random.RandomState(3)

    def wave(lens):
        return [(ServeRequest(client=f.client,
                              tokens=_tokens(rng, cfg, n)), f.p)
                for f, n in zip(frags, lens)]

    def check(reqs):
        for req, _ in reqs:
            want, _ = M.forward(params, cfg, np.asarray(req.tokens)[None])
            np.testing.assert_allclose(req.result, np.asarray(want[0]),
                                       atol=ATOL, rtol=RTOL)

    with GraftExecutor(mixed_depth_plan(cfg, book, frags, s=1),
                       params, cfg, packed=True) as ex:
        reqs = wave((5, 9, 16))
        ex.serve(reqs)
        check(reqs)
        st = ex.pool_stats()
        assert all(s["packed"] for s in st.values())
        assert sum(s["real_tokens"] for s in st.values()) > 0
        # realign mid-run: the shared boundary moves from 1 to 2
        frags2 = [Fragment(cfg.name, min(f.p, 2), f.t, f.q, client=f.client)
                  for f in frags]
        ex.apply_plan(mixed_depth_plan(cfg, book, frags2, s=2))
        reqs = wave((12, 3, 8))
        ex.serve(reqs)
        check(reqs)


# ------------------------------------------------------- extras grouping

def test_mixed_extras_batch_per_request(dense):
    """THE regression: a flushed batch whose requests carry different
    extras must run each request under ITS OWN extras — never the first
    request's extras applied to the whole batch."""
    cfg = get_smoke_config("llama-3.2-vision-90b")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    L = M.n_fragment_units(cfg)
    inst = FragmentInstance(params, cfg, _spec(cfg, 0, L, batch=4))
    assert not inst.packed                # extras-carrying family: padded
    rng = np.random.RandomState(4)
    Timg = cfg.vision.n_image_tokens
    reqs = []
    for i in range(3):
        extras = {"images": rng.randn(1, Timg, cfg.d_model)
                  .astype(np.float32) * 0.02}
        req = ServeRequest(client=f"c{i}", tokens=_tokens(rng, cfg, 6),
                           extras=extras)
        inst.submit(req, np.asarray(req.tokens))
        reqs.append(req)
    got = dict((id(r), np.asarray(y)) for r, y in inst.flush())
    for req in reqs:
        want = M.run_fragment(params, cfg, np.asarray(req.tokens)[None],
                              0, L, extras=req.extras)
        np.testing.assert_allclose(got[id(req)], np.asarray(want[0]),
                                   atol=ATOL, rtol=RTOL)


def test_extras_shape_groups_never_share_a_batch():
    """Requests whose extras SHAPES differ split into separate
    executions (one stacked extras per signature group)."""
    cfg = get_smoke_config("llama-3.2-vision-90b")
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    L = M.n_fragment_units(cfg)
    inst = FragmentInstance(params, cfg, _spec(cfg, 0, L, batch=4))
    rng = np.random.RandomState(5)
    Timg = cfg.vision.n_image_tokens
    reqs = []
    for i, t in enumerate((Timg, 2 * Timg, Timg)):
        extras = {"images": rng.randn(1, t, cfg.d_model)
                  .astype(np.float32) * 0.02}
        req = ServeRequest(client=f"c{i}", tokens=_tokens(rng, cfg, 6),
                           extras=extras)
        inst.submit(req, np.asarray(req.tokens))
        reqs.append(req)
    got = dict((id(r), np.asarray(y)) for r, y in inst.flush())
    assert inst.n_batches == 2            # {Timg x2} and {2*Timg}
    for req in reqs:
        want = M.run_fragment(params, cfg, np.asarray(req.tokens)[None],
                              0, L, extras=req.extras)
        np.testing.assert_allclose(got[id(req)], np.asarray(want[0]),
                                   atol=ATOL, rtol=RTOL)


# ------------------------------------------------------- compile counting

def test_compile_counter_matches_jax_cache():
    """n_compiles counts ACTUAL jax compile events (jit cache growth),
    and distinct extras shapes — same keys — count separately."""
    cfg = get_smoke_config("olmo-1b")     # cold module cache for this cfg
    assert is_packable(cfg)
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    L = M.n_fragment_units(cfg)
    inst = FragmentInstance(params, cfg, _spec(cfg, 0, L, batch=4))
    fn = packed_fragment_fn(cfg, L, True, True)
    cache0 = fn._cache_size()
    rng = np.random.RandomState(6)
    for lens in ((3, 4), (5, 2), (9, 9, 9)):  # buckets 8, 8, 32
        for n in lens:
            req = ServeRequest(client="c", tokens=_tokens(rng, cfg, n))
            inst.submit(req, np.asarray(req.tokens))
        inst.flush()
    buckets = {token_bucket(7), token_bucket(27)}
    assert inst.n_compiles == len(buckets) == 2
    assert fn._cache_size() - cache0 == inst.n_compiles


def test_compile_counter_sees_extras_shapes():
    cfg = get_smoke_config("llama-3.2-vision-90b")
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    L = M.n_fragment_units(cfg)
    inst = FragmentInstance(params, cfg, _spec(cfg, 0, L, batch=1))
    rng = np.random.RandomState(7)
    Timg = cfg.vision.n_image_tokens
    for t in (Timg, 2 * Timg):            # same extras KEYS, new shape
        req = ServeRequest(client="c", tokens=_tokens(rng, cfg, 6),
                           extras={"images": rng.randn(1, t, cfg.d_model)
                                   .astype(np.float32) * 0.02})
        inst.submit(req, np.asarray(req.tokens))
        inst.flush()
    assert inst.n_compiles == 2           # keys-only hashing said 1 here


# ------------------------------------------------------- phantom uplink

class _FakeStats:
    def __init__(self, samples):
        self.samples = list(samples)


class _FakeChannel:
    def __init__(self, samples=()):
        self.stats = _FakeStats(samples)

    def request(self, msg):
        return {"ok": True}


def test_submit_without_sample_returns_none():
    h = PoolHandle(("m", 0, 1), _FakeChannel())
    assert h.submit(1, "c0", np.zeros(4, np.int32)) is None


def test_submit_with_sample_returns_it():
    h = PoolHandle(("m", 0, 1), _FakeChannel([(0.0, 1024, 2.0)]))
    assert h.submit(1, "c0", np.zeros(4, np.int32)) == (1024, 2.0)


def test_controller_first_estimate_from_real_sample():
    """The controller's first bandwidth estimate must come from a real
    measured transfer — an unmeasured hop contributes NOTHING (the old
    phantom (0, 0.0) sample seeded the window with garbage)."""
    from repro.core import default_book
    from repro.serving.controller import ServingController

    ctl = ServingController(default_book())
    ctl.observe_arrival(0.0, "c0", "inc", p=2, budget_ms=100.0)
    # unmeasured hop: submit returned None, the caller records nothing
    ctl.ingest_uplink(1.0, [])
    assert ctl.estimates(2.0)["c0"].bw == 0.0
    # defense in depth: a zero-valued sample is ignored at the window too
    ctl.observe_uplink(3.0, "c0", 0, 0.0)
    assert ctl.estimates(4.0)["c0"].bw == 0.0
    ctl.observe_uplink(5.0, "c0", 1_000_000, 10.0)
    assert ctl.estimates(6.0)["c0"].bw == pytest.approx(1e8)


# ------------------------------------------------------- bucket policies

def test_bucket_policies():
    for n in range(1, 40):
        b = bucket_size(n, 8)
        assert b >= min(n, 8) and (b == n if n >= 8 else b <= 8)
        s = seq_bucket(n)
        assert s >= max(n, 8) and (s & (s - 1)) == 0     # pow2, >= floor
        t = token_bucket(n)
        assert t >= n
        if n <= 8:
            assert t == 8
        else:
            assert t % 16 == 0 and t - n < 16            # bounded waste
    # monotone
    for f in (bucket_size, seq_bucket, token_bucket):
        args = (lambda n: (n, 8)) if f is bucket_size else (lambda n: (n,))
        vals = [f(*args(n)) for n in range(1, 100)]
        assert all(a <= b for a, b in zip(vals, vals[1:]))


def test_padding_waste_accounting(dense):
    cfg, params = dense
    L = M.n_fragment_units(cfg)
    rng = np.random.RandomState(8)

    packed = FragmentInstance(params, cfg, _spec(cfg, 0, L), packed=True)
    for n in (3, 5):
        t = _tokens(rng, cfg, n)
        packed.submit(ServeRequest(client="c", tokens=t), np.asarray(t))
    packed.flush()
    assert (packed.real_tokens, packed.pad_tokens) == (8, 0)  # bucket 8

    padded = FragmentInstance(params, cfg, _spec(cfg, 0, L), packed=False)
    for n in (3, 5):
        t = _tokens(rng, cfg, n)
        padded.submit(ServeRequest(client="c", tokens=t), np.asarray(t))
    padded.flush()
    # both pad to the 8-token seq bucket and stack: 2*8 executed for 8 real
    assert (padded.real_tokens, padded.pad_tokens) == (8, 8)


# ------------------------------------------------------- property tests

@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_packed_layout_properties():
    hyp = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.lists(st.integers(1, 33), min_size=1, max_size=8))
    @settings(max_examples=200, deadline=None)
    def prop(lengths):
        total = sum(lengths)
        T = token_bucket(total)
        assert T >= total and T - total < 16
        seg, pos, cu = pack_segments(lengths, T)
        assert int(cu[-1]) == total
        for i, n in enumerate(lengths):
            assert (seg[int(cu[i]):int(cu[i + 1])] == i).all()
            np.testing.assert_array_equal(
                pos[int(cu[i]):int(cu[i + 1])], np.arange(n))
        assert (seg[total:] == len(lengths)).all()

    prop()


def test_packed_equals_per_request_random_mixes(dense):
    hyp = pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, params = dense
    L = M.n_fragment_units(cfg)

    @given(st.lists(st.integers(1, 12), min_size=1, max_size=4),
           st.integers(0, 2 ** 31 - 1))
    @settings(max_examples=8, deadline=None)
    def prop(lengths, seed):
        rng = np.random.RandomState(seed)
        payloads = [_tokens(rng, cfg, n) for n in lengths]
        packed = run_fragment_packed(params, cfg, payloads, 0, L,
                                     pad_to=token_bucket(sum(lengths)))
        for p, y in zip(payloads, packed):
            want = M.run_fragment(params, cfg, np.asarray(p)[None], 0, L)
            np.testing.assert_allclose(np.asarray(y), np.asarray(want[0]),
                                       atol=ATOL, rtol=RTOL)

    prop()


# ------------------------------------------------ packed vs padded traffic

def test_packed_beats_padded_on_ragged_mixes(dense):
    """The same seeded ragged mixes through a packed and a pad-to-bucket
    pool: packing executes fewer pad tokens and compiles fewer programs,
    with the same outputs."""
    cfg, params = dense
    L = M.n_fragment_units(cfg)
    mixes_rng = np.random.RandomState(7)
    mixes = [[int(mixes_rng.choice((8, 12, 16, 24)))
              for _ in range(mixes_rng.randint(1, 5))] for _ in range(4)]
    pools, outs = {}, {}
    for packed in (False, True):
        rng = np.random.RandomState(8)              # identical traffic
        inst = pools[packed] = FragmentInstance(
            params, cfg, _spec(cfg, 0, L), packed=packed)
        out = outs[packed] = {}
        for r, mix in enumerate(mixes):
            for i, S in enumerate(mix):
                req = ServeRequest(client=f"c{r}.{i}",
                                   tokens=_tokens(rng, cfg, S))
                inst.submit(req, req.tokens)
            out.update((req.client, np.asarray(y)) for req, y in inst.flush())
    assert pools[True].pad_tokens < pools[False].pad_tokens
    assert pools[True].n_compiles < pools[False].n_compiles
    assert pools[True].real_tokens == pools[False].real_tokens
    assert outs[True].keys() == outs[False].keys()
    for client, y in outs[True].items():
        np.testing.assert_allclose(y, outs[False][client], atol=ATOL,
                                   rtol=RTOL)
