#!/usr/bin/env bash
# Per-PR gate: tier-1 tests (minus slow subprocess compiles) and smokes
# of the real-transport demo, decode, disagg and routing paths, so
# scheduler/controller/transport regressions surface before merge. Speed
# is measured on the chip (chipbench/), never here.
#
#   ./scripts/ci.sh                # full gate (tests + smokes)
#   ./scripts/ci.sh --tests        # tests only
#   ./scripts/ci.sh --remote-smoke # multi-host-shaped serve loop: 2 front-ends
#                                  # over the SOCKET executor (worker
#                                  # subprocesses dialing back to
#                                  # --advertise-host 127.0.0.1)
#   ./scripts/ci.sh --decode-smoke # BLOCKING: in-process continuous-batching
#                                  # decode loop over the paged KV arena;
#                                  # every stream's tokens checked against the
#                                  # unbatched reference (exit 1 on mismatch)
#   ./scripts/ci.sh --disagg-smoke # BLOCKING: disaggregated decode end-to-end;
#                                  # a prefill-role pool hands KV blocks to a
#                                  # decode-role pool over the transport —
#                                  # token-exact vs the unbatched reference and
#                                  # >=1 cross-pool KV handoff required (exit 1
#                                  # on mismatch / zero handoffs / any local
#                                  # fallback)
#   ./scripts/ci.sh --route-smoke  # BLOCKING: routing subsystem end-to-end;
#                                  # one front-end wedged mid-traffic with a
#                                  # skewed burst queued against it — the
#                                  # survivor must steal the queued work and
#                                  # complete it with exact numerics (exit 1
#                                  # on zero steals / any shed / mismatch)
#   ./scripts/ci.sh --obs-smoke    # observability end-to-end: short serve loop
#                                  # with tracing + metrics on; asserts the
#                                  # trace is Perfetto-loadable and covers the
#                                  # request lifecycle, the metrics dump
#                                  # parses, and every replan has an audit
#                                  # entry (writes TRACE_ci.json /
#                                  # METRICS_ci.json for artifact upload)
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

if [[ "${1:-}" == "--decode-smoke" ]]; then
    python - <<'EOF'
import sys
from repro.serving.smoke import run_decode_smoke

report = run_decode_smoke(log=lambda *a: print(*a, flush=True))
ok = report["numerics_ok"] and report["numerics_checked"] > 0
dec = report.get("decode", {})
print(f"[decode-smoke] attainment={dec.get('attainment', 0.0):.2f} "
      f"checked={report['numerics_checked']}")
if not ok:
    print(f"[decode-smoke] FAIL: "
          f"{report.get('numerics_error', 'no streams completed')}",
          file=sys.stderr)
sys.exit(0 if ok else 1)
EOF
    exit $?
fi

if [[ "${1:-}" == "--disagg-smoke" ]]; then
    python - <<'EOF'
import sys
from repro.serving.smoke import run_disagg_smoke

report = run_disagg_smoke(log=lambda *a: print(*a, flush=True))
ok = (report["numerics_ok"] and report["numerics_checked"] > 0
      and report["kv_handoffs"] >= 1 and report["decode_local"] == 0)
if not ok:
    print(f"[disagg-smoke] FAIL: handoffs={report['kv_handoffs']} "
          f"local={report['decode_local']} "
          f"{report.get('numerics_error', '')}", file=sys.stderr)
sys.exit(0 if ok else 1)
EOF
    exit $?
fi

if [[ "${1:-}" == "--route-smoke" ]]; then
    python - <<'EOF'
import sys
from repro.serving.smoke import run_route_smoke

report = run_route_smoke(log=lambda *a: print(*a, flush=True))
ok = (report["numerics_ok"] and report["steals"] >= 1
      and report["shed"] == 0)
if not ok:
    print(f"[route-smoke] FAIL: steals={report['steals']} "
          f"shed={report['shed']} "
          f"{report.get('numerics_error', '')}", file=sys.stderr)
sys.exit(0 if ok else 1)
EOF
    exit $?
fi

if [[ "${1:-}" == "--obs-smoke" ]]; then
    # telemetry left ON through a real serve loop (decode client included
    # so decode/step spans land), then the artifacts are checked, not
    # just written: non-empty Chrome-trace JSON covering
    # ingest->queue->uplink->exec, a parseable metrics dump with live
    # histograms, and one audit entry per replan
    python -m repro.launch.serve --serve-loop --execute inprocess \
        --serve-seconds 3 --clients 3 --decode-tokens 4 \
        --trace-out TRACE_ci.json --metrics-dump METRICS_ci.json
    python - <<'EOF'
import json
import sys

trace = json.load(open("TRACE_ci.json"))
events = trace["traceEvents"]
kinds = {e["name"] for e in events if e.get("ph") == "X"}
need = {"ingest", "queue", "uplink", "exec", "request", "decode/step"}
missing = need - kinds
metrics = json.load(open("METRICS_ci.json"))
hists = metrics.get("histograms", {})
audit = metrics.get("audit", [])
n_spans = sum(1 for e in events if e.get("ph") == "X")
unstamped = [e for e in audit if e.get("apply_ms") is None]
print(f"[obs-smoke] {n_spans} spans ({len(kinds)} kinds), "
      f"{len(hists)} histograms, {len(audit)} audit entries")
ok = True
if not events or missing:
    print(f"[obs-smoke] FAIL: trace missing span kinds {sorted(missing)}",
      file=sys.stderr)
    ok = False
if not hists.get("server/latency_ms", {}).get("count"):
    print("[obs-smoke] FAIL: no latency histogram samples", file=sys.stderr)
    ok = False
if not audit or unstamped:
    print(f"[obs-smoke] FAIL: {len(unstamped)}/{len(audit)} audit entries "
          f"missing apply latency", file=sys.stderr)
    ok = False
sys.exit(0 if ok else 1)
EOF
    exit $?
fi

if [[ "${1:-}" == "--remote-smoke" ]]; then
    # the remote data path end-to-end: per-front-end worker channels,
    # numerics checked against the monolithic pass (exit 1 on mismatch)
    python -m repro.launch.serve --serve-loop --execute socket \
        --serve-seconds 2 --clients 2 --frontends 2 \
        --advertise-host 127.0.0.1
    exit $?
fi

python -m pytest -q -m "not slow"

if [[ "${1:-}" != "--tests" ]]; then
    # the demo path must not silently rot: tiny in-process transport run
    python examples/online_serving.py --transport inprocess --waves 2 \
        --clients 2
    # the event-driven runtime, wall-clock: ~2 s in-process serve loop with
    # a mid-traffic partition shift driving a timer replan
    python -m repro.launch.serve --serve-loop --execute inprocess \
        --serve-seconds 2 --clients 2
    # fleet topology: two front-ends over one executor, same loop
    python -m repro.launch.serve --serve-loop --execute inprocess \
        --serve-seconds 2 --clients 2 --frontends 2
    # the decode serving path must stay token-exact vs the unbatched
    # reference: continuous batching + paged KV, checked in-process
    "$0" --decode-smoke
    # the disaggregated decode path must stay token-exact too, with real
    # cross-pool KV handoffs (prefill pool -> frame -> decode pool)
    "$0" --disagg-smoke
    # the routing subsystem must keep stealing: wedge a front-end with
    # queued work, the survivor steals and completes it token-exact
    "$0" --route-smoke
fi
