"""The program's phases in a reduced trace: ``spans.py`` and the four
per-step readers on a hand-made trace whose numbers are known by
construction, and the four readers on one small traced run on the CPU."""
import json
from pathlib import Path

import pytest

from chipbench import run, spans
from chipbench.tests import small

# window [1000, 2000); a step runs from one decode/dispatch to the next.
# The dispatches at 1010, 1400 and 1800 start inside the window, so the
# whole steps are [1010, 1400) and [1400, 1800). A step begun before the
# window has phases straddling its start, and the last one's readback
# straddles its end: neither counts.
HAND = {
    "window": [1000, 2000],
    "chips": {},
    "host": [
        ["decode/dispatch", 900, 20],
        ["decode/readback", 950, 100],        # straddles the start
        ["kv/append", 990, 5],
        # step 1
        ["decode/dispatch", 1010, 10],
        ["decode/sync", 1020, 30],
        ["decode/readback", 1050, 100],
        ["decode/widen", 1150, 200],
        ["kv/append", 1360, 6],
        ["transport/pack", 1370, 2],
        ["transport/unpack", 1372, 2],
        ["server/decode_tick", 1380, 4],
        ["kv/write_prompt", 1385, 10],
        ["server/decode_admit", 1395, 3],
        # step 2
        ["decode/dispatch", 1400, 10],
        ["kv/gather", 1410, 8],
        ["decode/readback", 1450, 100],
        ["np.asarray(jax.Array)", 1460, 80],
        ["decode/widen", 1550, 150],
        ["transport/pack", 1710, 2],
        ["server/decode_tick", 1720, 4],
        # the last dispatch in the window; its readback straddles the end
        ["decode/dispatch", 1800, 10],
        ["decode/readback", 1950, 100],
        # after the window
        ["decode/dispatch", 2000, 10],
        ["transport/pack", 2010, 2],
    ],
}

READERS = {"readback_ms.decode": (100 + 200 + 100 + 150) / 2 / 1e6,
           "arena_ms.decode": (6 + 10 + 8) / 2 / 1e6,
           "hop_ms.decode": (2 + 2 + 2) / 2 / 1e6,
           "sched_ms.decode": (4 + 3 + 4) / 2 / 1e6}


def test_phase_ns_counts_by_start_at_whole_durations():
    assert spans.phase_ns(HAND, ["decode/dispatch"]) == (3, 30.0)
    assert spans.phase_ns(HAND, ["decode/readback"]) == (3, 300.0)
    assert spans.phase_ns(HAND, ["decode/readback"], (1010, 1800)) == \
        (2, 200.0)
    assert spans.phase_ns(HAND, ["kv/append", "kv/gather"]) == (2, 14.0)
    # names match exactly, not by prefix
    assert spans.phase_ns(HAND, ["decode/read"]) == (0, 0.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_ms_per_whole_step(name):
    assert run.metric_reader(name)({}, HAND) == pytest.approx(READERS[name])


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_is_none_without_a_whole_step(name):
    one_step = dict(HAND, host=[h for h in HAND["host"]
                                if h[0] != "decode/dispatch"
                                or h[1] not in (1400, 1800)])
    assert run.metric_reader(name)({}, one_step) is None
    # a program that writes no phases (the parent of this reader)
    assert run.metric_reader(name)({}, dict(HAND, host=[])) is None


def test_small_traced_run_reads_every_phase_metric():
    """One ``--trace 1`` run at small sizes on the CPU gives all four."""
    workload = "qwen3-decode-backlog"
    cell = json.loads((Path(__file__).resolve().parents[1] / "cells"
                       / f"{workload}.json").read_text())
    spec, cfg = small.config()
    line = run.run_cell(workload, 3_000_000_111, 1.0, True,
                        require_chip=False, spec=spec, cfg=cfg, cell=cell,
                        traffic=small.decode_traffic())
    assert line["correct"], line["checks"]
    for name in READERS:
        assert line["metrics"][name]["value"] > 0, name
        assert line["metrics"][name]["unit"] == "ms"
