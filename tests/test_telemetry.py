"""Telemetry: mergeable registry invariants, lock-free instrument
thread-safety, span propagation — across a socket-transport pool hop
and across a mid-traffic replan (with the audit log it must leave) —
and the phases the decode path writes into the profiler's trace."""
import json
import math
import threading

import numpy as np
import pytest

from repro.serving.telemetry import (GROWTH, Histogram, NULL, Telemetry,
                                     bucket_index, phase)

try:                     # minimal envs: property tests skip, the rest run
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


# ------------------------------------------------------- merge properties

def _state_of(vals):
    h = Histogram("x")
    for v in vals:
        h.record(v)
    return h.state()


def _check_merge_equals_concatenated(a, b):
    """THE merge contract: merge(state(a), state(b)) is bit-identical in
    buckets/count/min/max to one histogram fed the concatenated stream —
    so fleet-merged quantiles ARE the quantiles of all the samples."""
    merged = Histogram.merge_state(_state_of(a), _state_of(b))
    concat = _state_of(list(a) + list(b))
    assert merged["buckets"] == concat["buckets"]
    assert merged["count"] == concat["count"]
    assert merged["min"] == concat["min"]
    assert merged["max"] == concat["max"]
    assert math.isclose(merged["sum"], concat["sum"], rel_tol=1e-9)
    for q in (0.0, 0.25, 0.5, 0.9, 0.99, 1.0):
        assert Histogram.quantile_of(merged, q) == \
            Histogram.quantile_of(concat, q)


def _check_quantile_within_bucket_error(a, b, q):
    """Merged-bucket quantiles track the true concatenated-sample
    quantile to bucket resolution: the reported value is the geometric
    midpoint of the bucket holding the nearest-rank sample, so it is
    within a factor sqrt(GROWTH) of that sample."""
    merged = Histogram.merge_state(_state_of(a), _state_of(b))
    got = Histogram.quantile_of(merged, q)
    ref = sorted(a + b)[int(math.floor(q * (len(a) + len(b) - 1)))]
    slack = GROWTH ** 0.5 * (1 + 1e-6)
    assert ref / slack <= got <= ref * slack


if HAVE_HYPOTHESIS:
    samples_st = st.lists(
        st.floats(min_value=1e-6, max_value=1e9,
                  allow_nan=False, allow_infinity=False),
        min_size=1, max_size=200)

    @given(samples_st, samples_st)
    @settings(max_examples=80, deadline=None)
    def test_histogram_merge_equals_concatenated_stream(a, b):
        _check_merge_equals_concatenated(a, b)

    @given(samples_st, samples_st,
           st.sampled_from((0.25, 0.5, 0.9, 0.99)))
    @settings(max_examples=80, deadline=None)
    def test_merged_quantiles_within_bucket_error_of_true(a, b, q):
        _check_quantile_within_bucket_error(a, b, q)


def test_histogram_merge_seeded_sweep():
    """Deterministic fallback for the properties above (always runs,
    hypothesis or not): lognormal + pareto-ish streams of varied sizes."""
    rng = np.random.RandomState(11)
    for _ in range(40):
        a = list(np.exp(rng.randn(rng.randint(1, 120)) * 3.0))
        b = list(rng.pareto(1.5, rng.randint(1, 120)) + 1e-6)
        _check_merge_equals_concatenated(a, b)
        for q in (0.25, 0.5, 0.9, 0.99):
            _check_quantile_within_bucket_error(a, b, q)


def test_histogram_nonpositive_and_extremes():
    h = Histogram("x")
    for v in (-1.0, 0.0, 3.0):
        h.record(v)
    st_ = h.state()
    assert st_["buckets"].get(bucket_index(-1.0)) == 2   # ZERO_IDX bucket
    assert Histogram.quantile_of(st_, 0.0) == -1.0       # exact min
    assert Histogram.quantile_of(st_, 1.0) == 3.0        # exact max


# ----------------------------------------------- concurrency: lock-free inc

def test_counter_and_histogram_concurrent_threads():
    """Per-thread cells must lose nothing under concurrent increments —
    the increment path takes no lock, only cell creation does."""
    tel = Telemetry(process="t")
    c = tel.counter("hits")
    h = tel.histogram("lat")
    n_threads, n_iter = 8, 5000

    def work(i):
        for k in range(n_iter):
            c.inc()
            h.record(1.0 + (k % 7))

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value() == n_threads * n_iter
    st_ = h.state()
    assert st_["count"] == n_threads * n_iter
    assert st_["min"] == 1.0 and st_["max"] == 7.0


def test_merge_snapshot_is_idempotent_per_source():
    """Re-polling the same worker (beacon thread AND the final dump) must
    never double count: counters land as last-write-wins prefixed gauges,
    histograms adopt the source state wholesale."""
    worker = Telemetry(process="worker-1")
    worker.counter("pool/batches").inc(3)
    for v in (2.0, 4.0):
        worker.histogram("pool/exec_ms").record(v)
    front = Telemetry(process="front")
    front.histogram("pool/exec_ms").record(8.0)     # front's own sample
    for _ in range(3):                               # three polls, one truth
        front.merge_snapshot(worker.snapshot(), source="w1", prefix="w1/")
    assert front.gauge("w1/pool/batches").value() == 3
    st_ = front.histogram("pool/exec_ms").state()
    assert st_["count"] == 3                         # 2 worker + 1 local
    assert st_["min"] == 2.0 and st_["max"] == 8.0


def test_router_metrics_merge_into_fleet_dump():
    """The router's counters/gauges (``route/steals``,
    ``route/affinity_hits``, ``route/fallback_hrw``, per-FE
    ``route/queue_depth``) register through the mergeable registry: a
    front-end's snapshot merges into the fleet registry as prefixed
    gauges and the lot appears in ``metrics_dump``."""
    from repro.serving.router import WeightedRouter

    fe_tel = Telemetry(process="fe0")
    r = WeightedRouter(telemetry=fe_tel, hysteresis_ms=0.0)
    fes = ["fe0", "fe1"]
    r.route("c", fes, now_ms=0.0)                 # no signals -> fallback
    r.update("fe0", now_ms=0.0, queue_depth_ms=12.5, affinity=(7,))
    r.update("fe1", now_ms=0.0, queue_depth_ms=80.0)
    r.route("c", fes, now_ms=0.0, digest=(7,))    # weighted + affinity hit
    assert fe_tel.counter("route/fallback_hrw").value() == 1
    assert fe_tel.counter("route/weighted").value() == 1
    assert fe_tel.counter("route/affinity_hits").value() == 1
    assert fe_tel.gauge("route/fe0/queue_depth").value() == 12.5
    assert fe_tel.gauge("route/fe1/queue_depth").value() == 80.0

    fleet_tel = Telemetry(process="fleet")
    fleet_tel.counter("route/steals").inc(3)       # the fleet's own counter
    for _ in range(2):                             # idempotent re-poll
        fleet_tel.merge_snapshot(fe_tel.snapshot(), source="fe0",
                                 prefix="fe0/")
    assert fleet_tel.gauge("fe0/route/fallback_hrw").value() == 1
    assert fleet_tel.gauge("fe0/route/affinity_hits").value() == 1
    assert fleet_tel.gauge("fe0/route/fe0/queue_depth").value() == 12.5

    dump = fleet_tel.metrics_dump()
    assert dump["counters"]["route/steals"] == 3
    for g in ("fe0/route/fallback_hrw", "fe0/route/affinity_hits",
              "fe0/route/fe0/queue_depth", "fe0/route/fe1/queue_depth"):
        assert g in dump["gauges"], f"{g} missing from metrics_dump"


def test_null_telemetry_is_inert():
    assert not NULL.enabled and not NULL.want_trace(1)
    NULL.counter("x").inc()
    NULL.histogram("x").record(1.0)
    NULL.span("a", "b", 1.0)
    assert NULL.counter("x").value() == 0.0 and not NULL.spans


# ------------------------------------- span propagation: socket pool hop

@pytest.mark.slow
def test_span_propagation_across_socket_hop():
    """A trace-sampled request crossing a real socket hop closes its exec
    span on the WORKER side; the span and the worker's histograms ride
    the stats reply back and merge into the front-end registry exactly
    once (span drain is a hand-off, histogram adoption is idempotent)."""
    from repro.core.plandiff import PoolSpec
    from repro.serving import SocketTransport
    from repro.serving.executor import (FragmentInstance, PoolHandle,
                                        PoolService)
    from repro.serving.smoke import smoke_setup

    cfg, _book, params = smoke_setup()
    key = (cfg.name, 0, 2)
    spec = PoolSpec(key=key, share=10, batch=2, n_instances=1)
    wtel = Telemetry(process="worker-sim", trace=True)
    inst = FragmentInstance(params, cfg, spec, telemetry=wtel)
    inst.owns_telemetry = True       # private registry: stats may drain
    tp = SocketTransport()
    tp.serve("pool", PoolService(inst).handle)
    front = Telemetry(process="front", trace=True)
    ch = tp.connect("pool")
    try:
        h = PoolHandle(key, ch)
        rng = np.random.RandomState(0)
        items = [(rid, "c0",
                  rng.randint(0, cfg.vocab_size, 16).astype(np.int32),
                  None, front.want_trace(rid)) for rid in (1, 2)]
        out = h.execute(items)
        assert {rid for rid, _ in out} == {1, 2}

        snap = h.stats()["telemetry"]
        assert snap["process"] == "worker-sim"
        execs = [s for s in snap["spans"] if s["name"] == "exec"]
        assert execs and execs[0]["rid"] in (1, 2)
        assert execs[0]["tid"] == "pool/{}/{}-{}".format(*key)
        n_exec = snap["histograms"]["pool/exec_ms"]["count"]
        assert n_exec >= 1

        front.merge_snapshot(snap, source="w0", prefix="w0/")
        assert any(s["name"] == "exec" and s["pid"] == "worker-sim"
                   for s in front.spans)
        # drained spans are handed off: a re-poll sends nothing new, and
        # re-merging the fresh snapshot keeps histogram counts unchanged
        snap2 = h.stats()["telemetry"]
        assert not snap2["spans"]
        front.merge_snapshot(snap2, source="w0", prefix="w0/")
        assert front.histogram("pool/exec_ms").count() == n_exec
        # the merged registry exports one Perfetto timeline with both
        # processes named
        trace = front.chrome_trace()
        names = {e["args"]["name"] for e in trace["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert "worker-sim" in names
    finally:
        ch.close()
        tp.close()


# --------------------------------- spans + audit across a mid-traffic replan

def test_spans_and_audit_across_mid_traffic_replan(tmp_path):
    """The wall-clock loop with telemetry ON: a timer replan fires
    mid-traffic, every replan leaves an audit entry naming its triggers
    and diff with the apply latency stamped, spans keep flowing after
    the plan transition, and both artifacts parse."""
    from repro.serving import run_serve_loop

    tel = Telemetry(process="serve", trace=True)
    trace_p = tmp_path / "trace.json"
    metrics_p = tmp_path / "metrics.json"
    rep = run_serve_loop(seconds=1.5, n_clients=2, rate=8.0, seed=0,
                         shift_frac=0.5, control_period_ms=200.0,
                         telemetry=tel, trace_out=str(trace_p),
                         metrics_dump=str(metrics_p))
    assert rep["served"] > 0 and rep["numerics_ok"]
    assert rep["timer_replans"] >= 1, f"no timer replan fired: {rep}"

    audit = rep["audit"]
    assert audit, "replan fired but the audit log is empty"
    for e in audit:
        assert e["triggers"], "audit entry without a trigger name"
        assert {"add", "keep", "remove"} <= set(e["diff"])
        assert e["replan_ms"] >= 0.0 and "window" in e
    stamped = [e for e in audit if e["apply_ms"] is not None]
    assert len(stamped) >= rep["timer_replans"]

    kinds = {s["name"] for s in tel.spans}
    assert {"ingest", "queue", "uplink", "exec", "request"} <= kinds
    # full sampling: EVERY admitted request closed a request span — none
    # were dropped across the plan transitions (>= because the loop's
    # warmup requests complete outside the report window but still trace)
    n_request = sum(1 for s in tel.spans if s["name"] == "request")
    assert n_request >= rep["served"]

    trace = json.loads(trace_p.read_text())
    assert any(e["ph"] == "X" and e["name"] == "request"
               for e in trace["traceEvents"])
    dump = json.loads(metrics_p.read_text())
    assert dump["histograms"]["server/latency_ms"]["count"] >= \
        rep["served"]
    assert dump["histograms"]["replan/apply_ms"]["count"] >= len(stamped)
    assert len(dump["audit"]) == len(audit)


# ------------------------------------------- phases in the profiler trace

LAYERS = ("server", "transport", "decode", "kv")


def _host_phases(log_dir) -> list:
    """[[(name, start_ns, end_ns), ...] per host thread] of the phases in
    the profile under ``log_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns),
                    int(ev.start_ns + ev.duration_ns))
                   for ev in line.events
                   if ev.name.split("/", 1)[0] in LAYERS]
            if evs:
                out.append(evs)
    return out


@pytest.fixture(scope="module")
def decode_profile(tmp_path_factory):
    """A tiny decode pool behind a server, with a live registry, serving
    two streams inside a profile (after one warm stream outside it):
    (phases per thread, pool stats, registry)."""
    import jax

    from repro.serving.executor import GraftExecutor, ServeRequest
    from repro.serving.server import GraftServer
    from repro.serving.smoke import (decode_plan, smoke_fragments,
                                     smoke_setup)
    from repro.serving.transport import InProcessTransport

    cfg, book, params = smoke_setup(seq_len=8, seed=0)
    frags = smoke_fragments(cfg, 1, seed=0)
    tel = Telemetry(process="t")
    ex = GraftExecutor(decode_plan(cfg, book, frags, batch=2), params, cfg,
                       transport=InProcessTransport(), decode_ctx=32,
                       kv_blocks=32, kv_block_tokens=4, telemetry=tel)
    server = GraftServer(ex, book=book).start()
    rng = np.random.RandomState(5)

    def serve(n):
        for _ in range(n):
            server.submit(ServeRequest(
                client=frags[0].client,
                tokens=rng.randint(0, cfg.vocab_size, 8).astype(np.int32),
                max_new_tokens=4, tpot_budget_ms=1e5), 0, 1e5)
        assert server.join(timeout=120.0)

    log_dir = tmp_path_factory.mktemp("profile")
    try:
        serve(1)                                  # compiles outside
        jax.profiler.start_trace(str(log_dir))
        try:
            serve(2)
        finally:
            jax.profiler.stop_trace()
        stats = next(iter(ex.pool_stats().values()))
    finally:
        server.stop(drain=False, timeout=10.0)
        ex.close()
    return _host_phases(log_dir), stats, tel


def test_decode_path_writes_its_phases(decode_profile):
    threads, _, _ = decode_profile
    names = [n for evs in threads for n, _, _ in evs]
    assert {"decode/dispatch", "decode/sync", "decode/readback",
            "decode/prefill", "decode/row_copy", "kv/append",
            "kv/write_prompt", "transport/pack", "transport/unpack",
            "server/decode_tick", "server/decode_admit"} <= set(names)
    # K and V stay on the device: a step copies its tokens down once and
    # widens nothing, and books its tokens in the arena once
    assert "decode/widen" not in names
    steps = names.count("decode/dispatch")
    assert steps >= 3
    assert names.count("decode/readback") == steps
    assert names.count("kv/append") == steps


def test_phases_never_enclose_another_layers(decode_profile):
    threads, _, _ = decode_profile
    for evs in threads:
        for a, a0, a1 in evs:
            for b, b0, b1 in evs:
                if (a0, a1) != (b0, b1) and a0 <= b0 and b1 <= a1:
                    assert a.split("/")[0] == b.split("/")[0], \
                        f"{a} encloses {b}"


def test_d2h_bytes_counted_per_step(decode_profile):
    _, stats, tel = decode_profile
    # a step reads back its slots' tokens, an admission its first token
    slots = 2
    assert stats["d2h_bytes"] == (4 * slots * stats["decode_steps"]
                                  + 4 * stats["decode_admits"])
    assert tel.metrics_dump()["counters"]["pool/d2h_bytes"] == \
        stats["d2h_bytes"]


def test_phase_without_profiler_records_nothing():
    live = Telemetry(process="t", trace=True)
    with phase("decode/readback", bytes=8):
        pass
    assert not NULL.spans and not live.spans
    dump = NULL.metrics_dump()
    assert not dump["counters"] and not dump["histograms"]
