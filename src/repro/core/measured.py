"""Measured profiler: build LayerCosts by TIMING a real (reduced) model.

The paper's profiler measures latency/throughput per (batch, share) on
GPUs; here we time jitted per-block fragment execution on the local
devices and fit the two-parameter latency model the scheduler consumes:

    lat_l(b) ~ alpha_l + beta_l * b
    => weight_bytes_l = alpha_l * C_m,   flops_l = beta_l * C_f

so a measured profile plugs into exactly the same PerfProfile machinery
as the analytic one (shares rescale both terms, as MPS does).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.config import ModelConfig
from repro.core.costmodel import (LayerCosts, TARGET, COMPUTE_EFF,
                                  MEMORY_EFF, BYTES_PER_PARAM)
from repro.models import fragment_forward, n_fragment_units, make_extras


def _time_call(fn, *args, reps: int = 3, **kw) -> float:
    out = fn(*args, **kw)
    jax.tree.leaves(out)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
        jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / reps


def measure_layer_costs(cfg: ModelConfig, params, *, seq_len: int = 16,
                        batches=(1, 4), reps: int = 3,
                        mobile_slowdown: float = 200.0) -> LayerCosts:
    """Time per-block execution of a reduced model; return LayerCosts.

    mobile_slowdown scales server-measured latency into the synthetic
    mobile-device model (a Nano is ~O(100x) slower than a server chip).
    """
    import functools

    from repro.models import embed_tokens

    L = n_fragment_units(cfg)
    rng = np.random.RandomState(0)
    lat = np.zeros((len(batches), L))
    for bi, b in enumerate(batches):
        toks = rng.randint(0, cfg.vocab_size, (b, seq_len)).astype(np.int32)
        extras = make_extras(cfg, b) or None
        h = embed_tokens(params, cfg, jax.numpy.asarray(toks))
        for l in range(L):
            fn = jax.jit(functools.partial(fragment_forward, cfg=cfg,
                                           start=l, end=l + 1))
            lat[bi, l] = _time_call(fn, params, hidden=h, extras=extras,
                                    reps=reps)
    b0, b1 = batches[0], batches[-1]
    beta = np.maximum((lat[-1] - lat[0]) / max(b1 - b0, 1), 1e-9)
    alpha = np.maximum(lat[0] - beta * b0, 1e-9)
    flops = beta * TARGET.flops * COMPUTE_EFF
    weights = alpha * TARGET.hbm_bw * MEMORY_EFF
    act = np.full(L + 1, float(seq_len * cfg.d_model * BYTES_PER_PARAM))
    act[0] = seq_len * 4.0
    mobile = flops * mobile_slowdown
    return LayerCosts(name=cfg.name, n_layers=L, flops_per_item=flops,
                      weight_bytes=weights, act_bytes=act,
                      mobile_flops=mobile, input_bytes=float(act[0]))
