#!/usr/bin/env python3
"""Chip benchmark of the Graft serving system: one cell, one run.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. One process holds the chip: it finds the
cell in ``BENCHMARK.json``, its configuration, traffic mix and cell file
under ``chipbench/`` by name, makes the weights on the device from the
seed, warms up every shape the mix can reach, measures ``--seconds``,
checks the served answers against a float32 reference, and prints one
JSON line last: the end-to-end metrics with ``--trace 0``, the per-layer
metrics (read from the profiler trace and the program's counters) with
``--trace 1``. It exits non-zero without a result when JAX finds no TPU,
fewer chips than the cell asks for, or a device kind missing from
``chipbench/peaks.json``.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``, with every program persisted however
short its compile, so only a cell's first run in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "chipbench"
# the script's own directory must not shadow top-level modules (``trace``)
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]


def say(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot measure here; exit non-zero with no result."""


def load_cell(workload: str) -> tuple[dict, dict, dict, dict, dict]:
    """(benchmark, workload entry, configuration file, traffic file, cell
    file), each found by its name in ``BENCHMARK.json``."""
    from chipbench import model, traffic
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cell = json.loads((BENCH / "cells" / f"{workload}.json").read_text())
    return (bench, w, model.load_config(w["config"]),
            traffic.load(w["traffic"]), cell)


def driver(mix: dict):
    """The driver module that serves a traffic mix: ``drivers/<driver>.py``."""
    return importlib.import_module(f"chipbench.drivers.{mix['driver']}")


def metric_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def setup_jax() -> None:
    """Persistent compile cache at a fixed path, every entry kept."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(jax, chips: int, require_chip: bool) -> tuple[dict, dict]:
    """(device record, peak table row) of this machine, or Refused."""
    devs = jax.devices()
    dev = devs[0]
    if require_chip and dev.platform != "tpu":
        raise Refused(f"JAX found no TPU (default device {dev.platform}); "
                      "the benchmark runs on the chip only")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found "
                      f"{len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if dev.device_kind not in peaks:
        if require_chip:
            raise Refused(f"device kind {dev.device_kind!r} is not in "
                          f"chipbench/peaks.json ({sorted(peaks)})")
        peak = next(iter(peaks.values()))        # CPU tests only
    else:
        peak = peaks[dev.device_kind]
    return ({"platform": dev.platform, "kind": dev.device_kind,
             "count": chips}, peak)


def prepare(workload: str, seed: int, seconds: float, trace: bool, *,
            require_chip: bool = True, spec=None, traffic=None, cell=None,
            cfg=None):
    """(benchmark, workload entry, device record, driver context): the
    device checked, the compile cache set, the weights made."""
    bench, w, f_spec, f_traffic, f_cell = load_cell(workload)
    spec, traffic = spec or f_spec, traffic or f_traffic
    cell = cell or f_cell
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    if require_chip:
        setup_jax()
    import jax
    device, peaks = device_info(jax, w["chips"], require_chip)
    say(f"[device] {device} jax {jax.__version__}")
    from chipbench import model
    from chipbench.compiles import CompileLog
    from chipbench.drivers.common import Ctx
    from repro.kernels import ops
    if require_chip and ops.get_default_impl() != "pallas":
        raise Refused(f"kernels are {ops.get_default_impl()!r}, not pallas")
    log = CompileLog().install()
    cfg = cfg or model.program_config(spec)
    params = model.make_weights(spec, seed)
    model.check_layout(spec, cfg, params)
    jax.block_until_ready(params)
    say(f"[setup] {w['config']}: {model.n_params(spec):,} params, weights "
        f"made at {time.monotonic() - T_START:.1f}s")
    ctx = Ctx(workload=workload, spec=spec, traffic=traffic, cell=cell,
              cfg=cfg, params=params, seed=seed, seconds=seconds,
              trace=trace, peaks=peaks, compile_log=log,
              trace_dir=ROOT / ".chipbench" / "trace", t_start=T_START,
              say=say)
    return bench, w, device, ctx


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             **kw) -> dict:
    """One run; returns the result line as a dict. Tests pass a small
    ``spec``/``traffic``/``cell``/``cfg`` and ``require_chip=False``."""
    bench, w, device, ctx = prepare(workload, seed, seconds, trace, **kw)
    traffic, log = ctx.traffic, ctx.compile_log
    out = driver(traffic).run(ctx)
    log.uninstall()
    device["memory_peak_bytes"] = out.memory_peak_bytes
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed}
    if trace:
        from chipbench import trace as tr
        metrics = {}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            v = metric_reader(m["name"])(out.readings, out.trace)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tr.busy_ns(out.trace) / 1e9
        device["window_s"] = tr.window_ns(out.trace) / 1e9
        line["breakdown"] = {"device_ops": tr.top_ops(out.trace),
                             "idle_gaps": tr.idle_gaps(out.trace)}
    else:
        metrics = {}
        for m in bench["end_to_end"]:
            if workload not in m.get("workloads", [workload]):
                continue
            metrics[m["name"]] = {"value": out.e2e[m["name"]],
                                  "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = out.checks
    say(f"[done] {time.monotonic() - T_START:.1f}s after start, the "
        f"window closed at {out.readings['window_end_s']:.1f}s")
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except Refused as e:
        say(f"chipbench: {e}")
        return 1
    except FileNotFoundError as e:
        say(f"chipbench: missing file: {e}")
        return 1
    for name, c in line["checks"].items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
