"""Per-kernel allclose sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, ops
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import decode_attention
from repro.kernels.rwkv6_scan import wkv6_scan
from repro.kernels.ssm_scan import ssm_scan

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(key, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


@pytest.mark.parametrize("B,Sq,Sk,H,KV,hd", [
    (1, 64, 64, 1, 1, 32),
    (2, 128, 128, 4, 2, 32),
    (2, 96, 96, 6, 2, 64),       # non-pow2 seq
    (1, 256, 256, 8, 8, 16),     # MHA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 40])
def test_flash_attention(B, Sq, Sk, H, KV, hd, dtype, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = _rand(ks[0], (B, Sq, H, hd), dtype)
    k = _rand(ks[1], (B, Sk, KV, hd), dtype)
    v = _rand(ks[2], (B, Sk, KV, hd), dtype)
    want = ref.ref_attention(q, k, v, causal=True, window=window)
    got = flash_attention(q, k, v, causal=True, window=window,
                          block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=ATOL[dtype], rtol=1e-2)


def test_flash_attention_noncausal():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = _rand(ks[0], (2, 64, 4, 32))
    k = _rand(ks[1], (2, 96, 4, 32))
    v = _rand(ks[2], (2, 96, 4, 32))
    want = ref.ref_attention(q, k, v, causal=False)
    got = flash_attention(q, k, v, causal=False, block_q=32, block_k=32,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("window", [0, 24])
def test_attention_segment_mask_impls_agree(window):
    """Sequence-packing segment masks: naive oracle, chunked reference,
    and the Pallas kernel (interpret) all agree on a ragged packed
    batch — the invariant the packed serving path rests on."""
    B, S, H, KV, hd = 2, 96, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (B, S, H, hd))
    k = _rand(ks[1], (B, S, KV, hd))
    v = _rand(ks[2], (B, S, KV, hd))
    # three segments + a pad segment, different splits per row
    seg = jnp.stack([
        jnp.concatenate([jnp.full(40, 0), jnp.full(25, 1),
                         jnp.full(20, 2), jnp.full(11, 3)]),
        jnp.concatenate([jnp.full(10, 0), jnp.full(60, 1),
                         jnp.full(26, 2)]),
    ]).astype(jnp.int32)
    outs = {}
    for impl in ("naive", "reference", "pallas_interpret"):
        with ops.use_impl(impl):
            outs[impl] = np.asarray(ops.attention(
                q, k, v, causal=True, window=window, seg_ids=seg))
    np.testing.assert_allclose(outs["reference"], outs["naive"],
                               atol=2e-5, rtol=1e-3)
    np.testing.assert_allclose(outs["pallas_interpret"], outs["naive"],
                               atol=2e-5, rtol=1e-3)
    # and masking is real: dropping the mask changes the answer
    with ops.use_impl("naive"):
        unmasked = np.asarray(ops.attention(q, k, v, causal=True,
                                            window=window))
    assert not np.allclose(outs["naive"], unmasked, atol=1e-3)


@pytest.mark.parametrize("B,Sk,H,KV,hd", [
    (2, 256, 4, 2, 32),
    (3, 128, 8, 8, 64),
    (1, 512, 16, 2, 64),
])
@pytest.mark.parametrize("window", [0, 100])
def test_decode_attention(B, Sk, H, KV, hd, window):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = _rand(ks[0], (B, 1, H, hd))
    k = _rand(ks[1], (B, Sk, KV, hd))
    v = _rand(ks[2], (B, Sk, KV, hd))
    q_pos = jnp.arange(B, dtype=jnp.int32) * 37 + 60
    kv_pos = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32)[None], (B, Sk))
    kv_pos = jnp.where(kv_pos <= q_pos[:, None], kv_pos, -1)
    want = ref.ref_attention(q, k, v, q_pos=q_pos[:, None], kv_pos=kv_pos,
                             causal=True, window=window)
    got = decode_attention(q, k, v, q_pos, kv_pos, window=window,
                           block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


@pytest.mark.parametrize("B,T,H,hd", [(1, 32, 1, 16), (2, 128, 3, 32),
                                      (2, 96, 2, 64)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_wkv6(B, T, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    r = _rand(ks[0], (B, T, H, hd), scale=0.5)
    k = _rand(ks[1], (B, T, H, hd), scale=0.5)
    v = _rand(ks[2], (B, T, H, hd), scale=0.5)
    w = jax.nn.sigmoid(_rand(ks[3], (B, T, H, hd))) * 0.85 + 0.1
    u = _rand(ks[4], (H, hd), scale=0.1)
    s0 = _rand(ks[5], (B, H, hd, hd), scale=0.1)
    want_o, want_s = ref.ref_wkv6(r, k, v, w, u, s0)
    got_o, got_s = ref.chunked_wkv6(r, k, v, w, u, s0, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=1e-3)
    got_o, got_s = wkv6_scan(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=5e-5, rtol=1e-3)
    np.testing.assert_allclose(got_s, want_s, atol=5e-5, rtol=1e-3)


def test_wkv6_extreme_decay():
    """Strong decays hit the shared clamp; all impls must agree (no NaN)."""
    B, T, H, hd = 1, 64, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = _rand(ks[0], (B, T, H, hd), scale=0.5)
    k = _rand(ks[1], (B, T, H, hd), scale=0.5)
    v = _rand(ks[2], (B, T, H, hd), scale=0.5)
    w = jnp.full((B, T, H, hd), 1e-6)                     # way below clamp
    u = _rand(ks[3], (H, hd), scale=0.1)
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    want_o, _ = ref.ref_wkv6(r, k, v, w, u, s0)
    got_o, _ = wkv6_scan(r, k, v, w, u, s0, chunk=32, interpret=True)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=5e-4, rtol=1e-2)


@pytest.mark.parametrize("B,T,H,hd,N", [(1, 32, 1, 16, 8), (2, 128, 3, 32, 16),
                                        (2, 96, 2, 64, 16)])
def test_ssm_scan(B, T, H, hd, N):
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    x = _rand(ks[0], (B, T, H, hd), scale=0.5)
    dt = jax.nn.softplus(_rand(ks[1], (B, T, H))) * 0.2
    A = -jnp.abs(_rand(ks[2], (H,))) * 4
    Bm = _rand(ks[3], (B, T, N), scale=0.5)
    Cm = _rand(ks[4], (B, T, N), scale=0.5)
    h0 = _rand(ks[5], (B, H, hd, N), scale=0.1)
    want_y, want_h = ref.ref_ssm_scan(x, dt, A, Bm, Cm, h0)
    got_y, got_h = ref.chunked_ssm_scan(x, dt, A, Bm, Cm, h0, chunk=32)
    np.testing.assert_allclose(got_y, want_y, atol=5e-5, rtol=1e-3)
    got_y, got_h = ssm_scan(x, dt, A, Bm, Cm, h0, chunk=32, interpret=True)
    np.testing.assert_allclose(got_y, want_y, atol=5e-5, rtol=1e-3)
    np.testing.assert_allclose(got_h, want_h, atol=5e-5, rtol=1e-3)


def test_step_kernels_match_scan():
    """Single-token step fns == first step of the sequence kernels."""
    B, H, hd, N = 2, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(6), 8)
    r, k, v = (_rand(ks[i], (B, 1, H, hd), scale=0.5) for i in range(3))
    w = jax.nn.sigmoid(_rand(ks[3], (B, 1, H, hd))) * 0.8 + 0.15
    u = _rand(ks[4], (H, hd), scale=0.1)
    s0 = _rand(ks[5], (B, H, hd, hd), scale=0.1)
    o1, s1 = ref.ref_wkv6(r, k, v, w, u, s0)
    o2, s2 = ops.wkv6_step(r, k, v, w, u, s0)
    np.testing.assert_allclose(o2, o1, atol=1e-5)
    np.testing.assert_allclose(s2, s1, atol=1e-5)

    x = _rand(ks[6], (B, 1, H, hd), scale=0.5)
    dt = jax.nn.softplus(_rand(ks[7], (B, 1, H))) * 0.2
    A = -jnp.abs(jax.random.normal(ks[0], (H,)))
    Bm = _rand(ks[1], (B, 1, N), scale=0.5)
    Cm = _rand(ks[2], (B, 1, N), scale=0.5)
    h0 = _rand(ks[3], (B, H, hd, N), scale=0.1)
    y1, h1 = ref.ref_ssm_scan(x, dt, A, Bm, Cm, h0)
    y2, h2 = ops.ssm_step(x, dt, A, Bm, Cm, h0)
    np.testing.assert_allclose(y2, y1, atol=1e-5)
    np.testing.assert_allclose(h2, h1, atol=1e-5)


@pytest.mark.parametrize("B,S,H,KV,hd", [(1, 64, 2, 1, 32), (2, 96, 4, 2, 32),
                                         (1, 128, 8, 8, 16)])
@pytest.mark.parametrize("window", [0, 40])
def test_flash_attention_backward(B, S, H, KV, hd, window):
    """Pallas fwd+bwd kernels (custom_vjp) == autodiff of the oracle."""
    from repro.kernels.flash_attention_bwd import flash_attention_trainable
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = _rand(ks[0], (B, S, H, hd), scale=0.5)
    k = _rand(ks[1], (B, S, KV, hd), scale=0.5)
    v = _rand(ks[2], (B, S, KV, hd), scale=0.5)

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.ref_attention(
            q, k, v, causal=True, window=window)))

    def loss_fl(q, k, v):
        return jnp.sum(jnp.sin(flash_attention_trainable(
            q, k, v, True, window, None, 32, 32, True)))

    np.testing.assert_allclose(loss_fl(q, k, v), loss_ref(q, k, v),
                               rtol=1e-5, atol=1e-5)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-3)


def test_flash_trainable_through_ops():
    """ops.attention(impl=pallas_interpret) is differentiable end-to-end."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = _rand(ks[0], (1, 64, 2, 32), scale=0.5)
    k = _rand(ks[1], (1, 64, 2, 32), scale=0.5)
    v = _rand(ks[2], (1, 64, 2, 32), scale=0.5)

    def f(q):
        return jnp.sum(ops.attention(q, k, v, causal=True,
                                     impl="pallas_interpret"))
    g = jax.grad(f)(q)
    def fr(q):
        return jnp.sum(ops.attention(q, k, v, causal=True, impl="naive"))
    gr = jax.grad(fr)(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), atol=2e-5)


@pytest.mark.parametrize("size,preferred", [(12, 256), (256, 256),
                                            (272, 256), (300, 256),
                                            (512, 256), (640, 512),
                                            (1000, 512)])
def test_seq_tile_is_legal(size, preferred):
    """Tiles span the whole (padded) axis or are 128-multiples dividing
    it — the only sequence tiles the TPU compiler accepts."""
    tile, padded = ops._seq_tile(size, preferred)
    assert padded >= size and padded - size < 128 and padded % tile == 0
    assert tile == padded or (tile % 128 == 0 and tile <= preferred)


@pytest.mark.parametrize("Sq,Sk,causal,packed", [
    (300, 300, True, False),     # unpacked prefill, padded to 384
    (300, 300, True, True),      # packed total, padded
    (264, 300, False, False),    # cross attention: keys padded
])
def test_attention_padded_tiles_exact(Sq, Sk, causal, packed):
    """Lengths without a legal tile are padded; the pad never leaks into
    real rows."""
    H, KV, hd = 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = _rand(ks[0], (1, Sq, H, hd))
    k = _rand(ks[1], (1, Sk, KV, hd))
    v = _rand(ks[2], (1, Sk, KV, hd))
    seg = (jnp.arange(Sq) >= 100).astype(jnp.int32)[None] if packed else None
    got, want = (ops.attention(q, k, v, causal=causal, seg_ids=seg, impl=i)
                 for i in ("pallas_interpret", "naive"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-3)


@pytest.mark.parametrize("Sq,Sk,causal,window", [
    (300, 300, True, 0),         # padded to 384, tiles of 128
    (300, 300, True, 40),        # sliding window over the pad
    (264, 300, False, 0),        # cross attention: pad keys masked
])
def test_attention_grad_padded_exact(Sq, Sk, causal, window):
    """Lengths without a legal tile stay differentiable through the pad:
    the Pallas backward's grads equal autodiff of the oracle."""
    H, KV, hd = 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = _rand(ks[0], (1, Sq, H, hd), scale=0.5)
    k = _rand(ks[1], (1, Sk, KV, hd), scale=0.5)
    v = _rand(ks[2], (1, Sk, KV, hd), scale=0.5)

    def loss(impl):
        return lambda q, k, v: jnp.sum(jnp.sin(ops.attention(
            q, k, v, causal=causal, window=window, impl=impl)))

    got, want = (jax.grad(loss(i), argnums=(0, 1, 2))(q, k, v)
                 for i in ("pallas_interpret", "naive"))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=1e-3)


def test_attend_cache_padded_context():
    """A decode context without a legal tile is padded with unwritten
    (kv_pos == -1) slots."""
    B, Sk, H, KV, hd = 3, 600, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(10), 3)
    q = _rand(ks[0], (B, 1, H, hd))
    k = _rand(ks[1], (B, Sk, KV, hd))
    v = _rand(ks[2], (B, Sk, KV, hd))
    q_pos = jnp.asarray([5, 300, 599], jnp.int32)
    kv_pos = jnp.broadcast_to(jnp.arange(Sk, dtype=jnp.int32)[None], (B, Sk))
    kv_pos = jnp.where(kv_pos <= q_pos[:, None], kv_pos, -1)
    got, want = (ops.attend_cache(q, k, v, q_pos, kv_pos, impl=i)
                 for i in ("pallas_interpret", "naive"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


def test_impl_choice_is_process_wide():
    """The backend picks the kernels; an override reaches every thread
    (pool drivers trace their programs off the main thread)."""
    import threading
    assert ops.backend_impl() == "reference"          # CPU test backend
    seen = []
    with ops.use_impl("pallas_interpret"):
        t = threading.Thread(target=lambda: seen.append(
            ops.get_default_impl()))
        t.start()
        t.join(timeout=10)
    assert seen == ["pallas_interpret"]
    assert ops.get_default_impl() == "reference"
    with pytest.raises(ValueError):
        with ops.use_impl("cuda"):
            pass
