"""The reduction of a profiler trace to the benchmark's device numbers,
on a hand-made trace whose numbers are known by construction and on a
slice of a recorded chip trace checked against a per-nanosecond count."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"

# window [1000, 2000); one chip:
#   a loop op [1100, 1500) whose body ops are [1150, 1250) and [1300, 1400)
#   a kernel [1600, 1700), and an op [1950, 2100) cut by the window's end,
#   and an op [900, 1050) cut by its start
HAND = {
    "window": [1000, 2000],
    "chips": {"/device:TPU:0": {
        "ops": [
            ["%while.1 = (s32[]) while(%t), body=%b", 1100, 400],
            ["%fusion.2 = f32[8] fusion(f32[8] %p), kind=kLoop", 1150, 100],
            ["%fusion.3 = f32[8] fusion(f32[8] %p), kind=kLoop", 1300, 100],
            ["%decode_attention.5 = bf16[8] custom-call(%q), "
             "custom_call_target=\"tpu_custom_call\"", 1600, 100],
            ["%copy.7 = f32[8] copy(f32[8] %x)", 1950, 150],
            ["%copy.8 = f32[8] copy(f32[8] %x)", 900, 150],
        ],
        "modules": [["jit__lambda(123)", 1100, 400],
                    ["jit_step(9)", 1600, 100],
                    ["jit_step(9)", 1950, 150],
                    ["jit_early(1)", 900, 150]]}},
    "host": [["np.asarray(jax.Array)", 1500, 100],
             ["PjitFunction(step)", 1690, 300],
             ["chipbench/everything", 0, 5000]],
}


def test_busy_and_window():
    # 50 (cut op) + 400 (loop) + 100 (kernel) + 50 (cut op)
    assert tr.busy_ns(HAND) == 600
    assert tr.window_ns(HAND) == 1000


def test_kernel_time_and_module_runs():
    assert tr.op_ns(HAND, r"^%decode_attention\b.*tpu_custom_call") == 100
    assert tr.op_ns(HAND, r"^%fusion\.") == 200
    # runs that start inside the window, whole durations
    assert tr.module_runs(HAND, r"^jit__lambda\(") == (1, 400.0)
    assert tr.module_runs(HAND, r"^jit_step\(") == (2, 250.0)
    assert tr.module_runs(HAND, r"^jit_early\(") == (0, 0.0)


def test_top_ops_are_self_times():
    top = dict(tr.top_ops(HAND))
    # the loop's own time is its span less its body's
    assert top["jit__lambda/%while.1 while"] == pytest.approx(200e-9)
    assert top["jit__lambda/%fusion.2 fusion"] == pytest.approx(100e-9)
    assert top["jit_step/%decode_attention.5 custom-call"] == \
        pytest.approx(100e-9)
    assert top["jit_step/%copy.7 copy"] == pytest.approx(50e-9)
    assert top["jit_early/%copy.8 copy"] == pytest.approx(50e-9)
    # self times add up to the busy time
    assert sum(top.values()) == pytest.approx(tr.busy_ns(HAND) / 1e9)


def test_idle_gaps_named_by_the_host_event_under_them():
    gaps = tr.idle_gaps(HAND)
    # [1700, 1950) under the step call, [1500, 1600) under the copy out,
    # [1050, 1100) under nothing that does not span the whole window
    assert gaps == [["PjitFunction(step)", pytest.approx(250e-9)],
                    ["np.asarray(jax.Array)", pytest.approx(100e-9)],
                    ["idle", pytest.approx(50e-9)]]


def _per_ns(trace) -> np.ndarray:
    """A boolean per nanosecond of the window: some op ran on chip 0."""
    w0, w1 = trace["window"]
    busy = np.zeros(w1 - w0, bool)
    chip = next(iter(trace["chips"].values()))
    for _, start, dur in chip["ops"]:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            busy[a - w0:b - w0] = True
    return busy


@pytest.mark.parametrize("name", sorted(p.name for p in
                                        DATA.glob("trace_*.json")))
def test_recorded_trace(name):
    rec = json.loads((DATA / name).read_text())
    trace, want = rec["trace"], rec["expect"]
    busy = _per_ns(trace)
    assert tr.busy_ns(trace) == busy.sum()
    assert 100.0 * (1 - tr.busy_ns(trace) / tr.window_ns(trace)) == \
        pytest.approx(want["idle_pct"], abs=1e-6)
    assert tr.op_ns(trace, want["kernel"]) == want["kernel_ns"]
    top = tr.top_ops(trace)
    assert sum(s for _, s in tr.top_ops(trace, k=10**6)) == \
        pytest.approx(busy.sum() / 1e9)
    assert [n for n, _ in top[:3]] == want["top3"]
    gaps = tr.idle_gaps(trace)
    # the longest gap is the longest run of idle nanoseconds
    edges = np.flatnonzero(np.diff(np.r_[1, busy.astype(np.int8), 1]))
    runs = edges[1::2] - edges[::2]
    assert gaps[0][1] == pytest.approx(runs.max() / 1e9)
    assert gaps[0][0] == want["longest_gap_under"]
