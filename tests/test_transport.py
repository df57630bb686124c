"""Transport layer: frame round-trips for arbitrary dtypes/shapes, the
truncated/oversized error paths, loopback and socket channels, link
shaping, and the batch-0 drain semantics of pool instances."""
import socket

import numpy as np
import pytest

from repro.serving.transport import (
    FrameError, InProcessTransport, LinkShape, ShapedTransport,
    SocketTransport, TruncatedFrameError, decode_frame, encode_frame)

# ------------------------------------------------------------------ framing

DTYPES = ["float32", "float16", "float64", "int32", "int8", "uint8",
          "int64", "bool", "complex64", "bfloat16"]
SHAPES = [(), (0,), (1,), (7,), (3, 4), (2, 3, 5), (1, 16, 256)]


def _tree_equal(a, b):
    assert type(a) is type(b) or (isinstance(a, (list, tuple))
                                  and isinstance(b, (list, tuple)))
    if isinstance(a, dict):
        assert a.keys() == b.keys()
        for k in a:
            _tree_equal(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _tree_equal(x, y)
    elif isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(a, b, equal_nan=True)
    else:
        assert a == b


@pytest.mark.parametrize("dtype", DTYPES)
def test_frame_round_trip_dtypes_and_shapes(dtype):
    """Property-style: random arrays of every dtype/shape round-trip
    bit-exactly, including empty, 0-d, and non-contiguous inputs."""
    rng = np.random.RandomState(hash(dtype) % 2**31)
    for shape in SHAPES:
        a = np.asarray(rng.randn(*shape) * 100).astype(dtype)
        out = decode_frame(encode_frame({"x": a}))["x"]
        assert out.dtype == a.dtype and out.shape == a.shape
        assert np.array_equal(out, a, equal_nan=True)
        assert out.flags.writeable            # decoded arrays own their data
    # non-contiguous view round-trips as its contiguous copy
    base = (rng.randn(6, 8) * 10).astype(dtype)
    view = base[::2, 1::3]
    out = decode_frame(encode_frame({"x": view}))["x"]
    assert np.array_equal(out, view, equal_nan=True)


def test_zero_copy_receive_above_size_threshold():
    """Frames carrying large activations decode as read-only VIEWS into
    the received buffer (no per-array copy); small arrays still copy so
    they stay writable and don't pin frame buffers. The threshold is the
    boundary: one byte under copies, at-threshold does not."""
    from repro.serving.transport import ZEROCOPY_MIN_BYTES
    small = np.arange(ZEROCOPY_MIN_BYTES - 1, dtype=np.uint8)
    big = np.arange(ZEROCOPY_MIN_BYTES, dtype=np.uint8)
    out = decode_frame(encode_frame({"s": small, "b": big}))
    assert out["s"].flags.writeable and out["s"].base is None   # owned copy
    assert not out["b"].flags.writeable                          # view
    assert out["b"].base is not None, "large array was copied"
    assert np.array_equal(out["s"], small)
    assert np.array_equal(out["b"], big)
    # the socket path reads into ONE preallocated buffer and round-trips
    # the same way (values exact, large payloads zero-copy on receive)
    tp = SocketTransport()
    tp.serve("zc", lambda m: {"ok": True, "payload": m["payload"]})
    ch = tp.connect("zc")
    x = (np.arange(ZEROCOPY_MIN_BYTES // 4, dtype=np.float32)
         .reshape(2, -1))
    back = ch.request({"payload": x})["payload"]
    assert np.array_equal(back, x)
    assert not back.flags.writeable and back.base is not None
    ch.close()
    tp.close()


def test_frame_round_trip_nested_structures():
    rng = np.random.RandomState(0)
    msg = {"op": "init", "n": 3, "f": 2.5, "none": None, "flag": True,
           "list": [1, "two", None],
           "params": {"blocks": {"w": rng.randn(4, 4).astype(np.float32)},
                      "bias": [rng.randn(2).astype(np.float16)]},
           "blob": b"\x00\x01\xff"}
    out = decode_frame(encode_frame(msg))
    # msgpack maps tuples to lists; our message vocabulary only uses lists
    _tree_equal(out["params"], msg["params"])
    assert out["op"] == "init" and out["none"] is None
    assert out["blob"] == msg["blob"]
    assert out["list"] == [1, "two", None]


def test_truncated_frame_raises():
    wire = encode_frame({"x": np.arange(100, dtype=np.int32)})
    for cut in (3, 8, 20, len(wire) - 1):      # header and body truncations
        with pytest.raises(TruncatedFrameError):
            decode_frame(wire[:cut])


def test_oversized_frame_refused_on_both_ends():
    big = {"x": np.zeros(1024, dtype=np.float64)}
    with pytest.raises(FrameError):
        encode_frame(big, max_frame_bytes=256)
    # a peer declaring an oversized length is refused before the body read
    wire = encode_frame(big)
    with pytest.raises(FrameError) as ei:
        decode_frame(wire, max_frame_bytes=256)
    assert not isinstance(ei.value, TruncatedFrameError)


def test_garbage_header_is_oversized_not_hang():
    """Random bytes in the length prefix must error out, not allocate."""
    with pytest.raises(FrameError):
        decode_frame(b"\xff" * 64)


# --------------------------------------------------------------- loopback

def test_inprocess_transport_echo_and_stats():
    tp = InProcessTransport()
    tp.serve("echo", lambda m: {"ok": True, "payload": m["payload"] * 2})
    ch = tp.connect("echo")
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    out = ch.request({"payload": x})
    assert np.array_equal(out["payload"], x * 2)
    assert ch.stats.n_transfers == 1
    assert ch.stats.total_bytes > x.nbytes      # payload + framing overhead
    tp.stop("echo")
    with pytest.raises(KeyError):
        tp.connect("echo")


def test_inprocess_transport_respects_frame_cap():
    tp = InProcessTransport(max_frame_bytes=512)
    tp.serve("echo", lambda m: m)
    ch = tp.connect("echo")
    with pytest.raises(FrameError):
        ch.request({"payload": np.zeros(4096, dtype=np.float32)})


# ---------------------------------------------------------------- shaping

def test_shaped_transport_injects_trace_delay():
    class FlatTrace:
        def at(self, t):
            return 1e4                        # 10 kB/s: slow, deterministic

    tp = ShapedTransport(InProcessTransport(),
                         {"c0": LinkShape(trace=FlatTrace(), rtt_ms=6.0)},
                         clock=lambda: 0.0)
    tp.serve("pool", lambda m: {"ok": True})
    ch = tp.connect("pool")
    payload = np.zeros(10_000, dtype=np.uint8)      # ~10 kB -> ~1000 ms
    ch.request({"op": "submit", "client": "c0", "payload": payload})
    _, nbytes, ms = ch.stats.samples[-1]
    expect = 6.0 / 2 + nbytes / 1e4 * 1e3
    assert ms == pytest.approx(expect, rel=0.05)
    # a client with no shape entry is not delayed
    ch.request({"op": "submit", "client": "other", "payload": payload})
    _, _, ms2 = ch.stats.samples[-1]
    assert ms2 < expect / 10


def test_shaped_transport_feeds_controller_bw_estimate():
    from repro.core import default_book
    from repro.serving import ServingController
    ctl = ServingController(default_book())
    ctl.observe_arrival(0.0, "c0", "inc", 1, budget_ms=80.0)
    # 1 MB over 100 ms -> 10 MB/s uplink
    ctl.ingest_uplink(50.0, [("c0", 1_000_000, 100.0), ("ghost", 1, 1.0)])
    est = ctl.estimates(100.0)
    assert est["c0"].bw == pytest.approx(1e7, rel=1e-6)
    assert "ghost" not in est                 # transfers alone don't admit


# ----------------------------------------------------------------- sockets

@pytest.mark.slow
def test_socket_transport_echo():
    tp = SocketTransport()
    tp.serve("echo", lambda m: {"ok": True, "payload": m["payload"] + 1})
    ch = tp.connect("echo")
    for shape in [(4,), (16, 256), (3, 5, 7)]:
        x = np.random.RandomState(0).randn(*shape).astype(np.float32)
        out = ch.request({"payload": x})
        assert np.array_equal(out["payload"], x + 1)
    assert ch.stats.n_transfers == 3
    # connection reuse: one persistent socket served all requests
    ch2 = tp.connect("echo")                 # second connection also fine
    assert np.array_equal(
        ch2.request({"payload": np.zeros(2, np.float32)})["payload"],
        np.ones(2, np.float32))
    ch.close()
    ch2.close()
    tp.close()


@pytest.mark.slow
def test_socket_server_survives_client_disconnect_and_bad_frame():
    tp = SocketTransport()
    tp.serve("echo", lambda m: {"ok": True})
    # a client that connects and dies mid-frame must not kill the server
    host, port = tp._servers["echo"].addr
    raw = socket.create_connection((host, port))
    raw.sendall(b"\x00\x00\x00\x00\x00\x00\x00\x10partial")
    raw.close()
    ch = tp.connect("echo")
    assert ch.request({"x": 1})["ok"]
    ch.close()
    tp.close()


# ------------------------------------------- shared-pool routing ordering

def test_shared_pool_flush_order_does_not_double_execute():
    """A shared pool is depth 0 for anchor clients (empty align) but depth
    1 for aligned ones. When the anchor's chain flushes the shared pool
    before the aligned client's depth-1 turn, the aligned request's output
    must be routed by ITS chain position — re-submitting it would run the
    shared blocks twice."""
    from repro.core.fragment import Fragment
    from repro.core.planner import ExecutionPlan
    from repro.core.profiles import Allocation, EMPTY_ALLOC
    from repro.core.repartition import GroupPlan, StagePlan
    from repro.serving import GraftExecutor
    from repro.serving.smoke import (check_against_monolithic,
                                     smoke_requests, smoke_setup)

    cfg, _book, params = smoke_setup()
    alloc = Allocation(share=10, batch=2, n_instances=1, latency_ms=1.0,
                       throughput=1.0, resource=10.0)
    c0 = Fragment(cfg.name, 0, 60.0, 30.0, client="c0")  # aligned, FIRST
    c1 = Fragment(cfg.name, 1, 60.0, 30.0, client="c1")  # anchor: [shared]
    gp = GroupPlan(model=cfg.name, repartition_point=1,
                   shared=StagePlan(c1, 1, 2, 10.0, alloc),
                   aligns=(StagePlan(c0, 0, 1, 10.0, alloc),
                           StagePlan(c1, 1, 1, 10.0, EMPTY_ALLOC)))
    plan = ExecutionPlan(plans=[gp], total_resource=20.0, n_fragments_in=2,
                         n_fragments_merged=2, schedule_time_s=0.0)
    with GraftExecutor(plan, params, cfg) as ex:
        assert [len(c) for c in ex._chains.values()] == [2, 1]
        reqs = smoke_requests(cfg, [c0, c1], seed=3)
        ex.serve(reqs)
        check_against_monolithic(cfg, params, reqs)


# ----------------------------------------------------- batch-0 drain path

def test_pool_drain_rejects_enqueue_and_empties_queue():
    """A pool retargeted to batch 0 refuses new work but still flushes
    what it holds — the remote-worker drain path must never hang."""
    import dataclasses
    from repro.core.plandiff import PoolSpec
    from repro.serving import PoolDrainingError, ServeRequest
    from repro.serving.executor import FragmentInstance, PoolService
    from repro.serving.smoke import smoke_setup

    cfg, _book, params = smoke_setup()
    key = (cfg.name, 0, 2)
    spec = PoolSpec(key=key, share=10, batch=2, n_instances=1)
    inst = FragmentInstance(params, cfg, spec)
    rng = np.random.RandomState(0)
    toks = lambda: rng.randint(0, cfg.vocab_size, 16).astype(np.int32)
    inst.submit(ServeRequest(client="a", tokens=None), toks())
    inst.submit(ServeRequest(client="b", tokens=None), toks())

    inst.retarget(dataclasses.replace(spec, batch=0, n_instances=0))
    assert inst.draining
    with pytest.raises(PoolDrainingError):
        inst.submit(ServeRequest(client="c", tokens=None), toks())
    out = inst.flush()                       # queued work drains at batch 1
    assert len(out) == 2 and not inst.queue

    # resuming with a real batch re-opens intake
    inst.retarget(dataclasses.replace(spec, batch=2))
    inst.submit(ServeRequest(client="c", tokens=None), toks())
    assert len(inst.queue) == 1

    # the same contract holds across the wire protocol
    svc = PoolService(inst)
    reply = svc.handle({"op": "retarget", "key": list(key), "share": 10,
                        "batch": 0, "n_instances": 0})
    assert reply["ok"]
    reply = svc.handle({"op": "submit", "req_id": 9, "client": "d",
                        "payload": toks(), "extras": None})
    assert not reply["ok"] and reply["etype"] == "PoolDrainingError"


def test_executor_drain_discards_stranded_requests():
    """drain() empties pool queues and reclaims in-flight bookkeeping —
    the recovery path after an aborted serve()."""
    from repro.core import GraftPlanner
    from repro.serving import GraftExecutor, ServeRequest
    from repro.serving.smoke import smoke_fragments, smoke_setup

    cfg, book, params = smoke_setup()
    frags = smoke_fragments(cfg, 2, seed=1)
    ex = GraftExecutor(GraftPlanner(book).plan(frags), params, cfg)
    rng = np.random.RandomState(0)
    req = ServeRequest(client=frags[0].client,
                       tokens=rng.randint(0, cfg.vocab_size, 16)
                       .astype(np.int32))
    # strand a request: queued in its first-hop pool, tracked, not served
    handle = ex._chains[req.client][0]
    ex._by_rid[123] = req
    handle.submit(123, req.client, ex.mobile_part(req, frags[0].p))
    assert handle.queue_len() == 1
    assert ex.drain() == 1
    assert handle.queue_len() == 0 and not ex._by_rid
    assert req.result is None                 # discarded, not completed
    ex.close()
