"""Pieces every driver uses: the run's context, timed requests, the
traced window, and the float32 reference over the served answers."""
from __future__ import annotations

import contextlib
import gc
import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import numpy as np


@dataclass
class Ctx:
    """What a driver is given: the cell as the files state it, the
    program's config, the seeded weights, and the run's options."""
    workload: str
    spec: dict                  # configuration file
    traffic: dict               # traffic mix file
    cell: dict                  # the cell's own file (rate, limits)
    cfg: object                 # the program's ModelConfig
    params: object              # seeded weights (made by the harness)
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    compile_log: object
    trace_dir: Path
    t_start: float              # process start (monotonic clock)
    say: object = print


@dataclass
class Outcome:
    """What a driver hands back to the harness."""
    e2e: dict                   # end-to-end metric -> value, setup_s too
    readings: dict              # raw numbers the per-layer readers use
    checks: dict                # name -> {"value", "limit"}
    correct: bool
    attempted: int
    failed: int
    memory_peak_bytes: int
    trace: Optional[dict] = None     # reduced device trace (--trace 1)
    served: list = field(default_factory=list)  # what the check compared


def timed_request_class():
    """A ServeRequest that stamps the moment the program sets its
    ``result``."""
    from repro.serving.executor import ServeRequest

    class TimedRequest(ServeRequest):
        def __setattr__(self, name, value):
            if name == "result" and value is not None:
                object.__setattr__(self, "t_done", time.monotonic())
            object.__setattr__(self, name, value)

    return TimedRequest


def pool_counters(ex) -> dict:
    """{pool key: stats} read through the pools' own stats op."""
    return {k: {n: s[n] for n in ("n_batches", "n_compiles", "real_tokens",
                                  "pad_tokens", "decode_admits",
                                  "decode_steps", "decode_tokens")}
            for k, s in ex.pool_stats().items()}


def delta(a: dict, b: dict) -> dict:
    return {k: {n: b[k][n] - a[k][n] for n in b[k]} for k in b}


@contextlib.contextmanager
def traced(ctx: Ctx, out: dict):
    """Profile the block into ``ctx.trace_dir`` when ``ctx.trace``, and
    reduce it into ``out`` afterwards (``out['trace']``)."""
    if not ctx.trace:
        yield
        return
    import jax
    from chipbench import trace as tr
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0          # python calls would swamp it
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(ctx.trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()
    out["trace"] = tr.load_dir(ctx.trace_dir)
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)


def join_server(server, timeout: float = 60.0) -> None:
    """Stop the server's threads and wait for each to end."""
    server.stop(drain=False, timeout=timeout)
    threads = list(getattr(server, "_threads", []))
    threads += list(getattr(server, "_drivers", {}).values())
    for t in threads:
        t.join(timeout)


def memory_peak_bytes() -> int:
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def free_program(*objs) -> None:
    """Drop the program's state before the reference runs on the chip."""
    for o in objs:
        close = getattr(o, "close", None)
        if close is not None:
            close()
    del objs
    gc.collect()


def sample(rng, items: list, k: int, longest) -> list:
    """``k`` of ``items`` drawn by ``rng``, always with the longest."""
    if len(items) <= k:
        return list(items)
    top = max(items, key=longest)
    rest = [x for x in items if x is not top]
    pick = rng.choice(len(rest), k - 1, replace=False)
    return [top] + [rest[i] for i in sorted(pick)]


# ------------------------------------------------------------ reference

def gaps_fn(spec: dict, mode: str):
    """Jitted reference over one padded row: per position in [lo, hi), how
    far the reference logit of ``targets`` (or, with ``mode`` the control,
    of the control's own top token) lies below the reference's best, in
    standard deviations of the reference's logits at that position; 0
    elsewhere. The reference is the one the configuration file names."""
    import jax
    import jax.numpy as jnp
    from chipbench import model
    ref_mod = model.reference(spec)

    def gaps(weights, tokens, targets, lo, hi):
        ref = ref_mod.logits(weights, spec, tokens)
        best = ref.max(-1)
        if mode == "ref":
            pick = targets
        else:
            pick = ref_mod.logits(weights, spec, tokens, mode).argmax(-1)
        got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
        idx = jnp.arange(tokens.shape[0])
        return jnp.where((idx >= lo) & (idx < hi),
                         (best - got) / ref.std(-1), 0.0)

    return jax.jit(gaps)
