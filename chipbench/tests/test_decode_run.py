"""A whole decode run at small sizes on the CPU, past the harness's look
for a chip: a sound run is correct; with a fault planted in the timed
decode path (``chipbench/faults.py``), or with the reference's fp8
control in the program's place, it is not."""
import json
from pathlib import Path

import pytest

from chipbench import faults
from chipbench.tests import small

WORKLOAD = "qwen3-decode-backlog"
CELL = json.loads((Path(__file__).resolve().parents[1] / "cells"
                   / f"{WORKLOAD}.json").read_text())


def _fails_a_limit(numbers: dict) -> bool:
    return any(numbers[k] > v for k, v in CELL["limits"].items())


def _line(seed):
    return small.run(WORKLOAD, seed, traffic_spec=small.decode_traffic(),
                     cell=CELL)


def test_sound_run_is_correct():
    line = _line(3_000_000_101)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["checks"]) == set(CELL["limits"])
    assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    assert set(line["metrics"]) == {"decode_tok_s", "setup_s"}
    assert line["metrics"]["decode_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    undo = faults.plant(fault)
    try:
        line = _line(3_000_000_102)
    finally:
        undo()
    assert not line["correct"]
    assert _fails_a_limit({k: c["value"] for k, c in line["checks"].items()})


def test_fp8_control_fails_a_limit():
    # a window as long as it takes to serve some hundred tokens
    ctx, driver, out = small.outcome(WORKLOAD, 3_000_000_103,
                                     traffic_spec=small.decode_traffic(),
                                     cell=CELL, seconds=3.0)
    assert out.correct
    assert _fails_a_limit(driver.control(ctx, out.served))
