"""The one traffic generator: ``requests(mix, seed, vocab)`` reads a mix's
parameter file (``traffic/<mix>.json``) and makes the run's requests.

Every size and gap is a quantile (i + 1/2)/n of the stated distribution,
one full set per block of ``block`` requests, so every seed serves the
same multiset of sizes and gaps; the seed only orders them inside each
block and draws the token ids. A mix states, as data:

``prompt_tokens``, ``output_tokens``
    a lognormal ``{"median", "sigma", "min", "max", "round_to"}``, or a
    mixture ``{"mix": [{"share": s, <lognormal>}, ...]}``.
``arrivals``
    ``{"process": "backlog"}``: every request due at once;
    ``{"process": "poisson", "rate_per_s": r}``: open loop at rate r;
    ``{"process": "on_off", "rate_per_s": r, "burst": b, "period_s": p}``:
    open loop at b * r during the first p / b seconds of each period and
    silent for the rest, r on average.
``shared_prefix`` (optional)
    ``{"tokens": n, "groups": g}``: request i's prompt starts with the n
    tokens of group i mod g, then its own drawn length.
``blocks`` (optional)
    how many blocks; else as many whole blocks as the rate fits into the
    run's seconds, at least one.

Which driver serves the mix is its ``driver`` key (``drivers/<name>.py``).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent


def load(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def _shares(shares: list[float], n: int) -> list[int]:
    """``n`` split by ``shares`` (largest remainders)."""
    w = np.asarray(shares, float)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def sizes(dist: dict, n: int) -> list[int]:
    """The ``n`` sizes of one block: quantiles of a lognormal clipped to
    [min, max] and rounded up to ``round_to``, or of each component of a
    mixture in proportion to its share."""
    if "mix" in dist:
        parts = dist["mix"]
        counts = _shares([c["share"] for c in parts], n)
        return [s for c, k in zip(parts, counts) for s in sizes(c, k)]
    nd = NormalDist()
    step = int(dist.get("round_to", 1))
    out = []
    for i in range(n):
        x = dist["median"] * math.exp(dist["sigma"]
                                      * nd.inv_cdf((i + 0.5) / n))
        x = min(max(x, dist["min"]), dist["max"])
        out.append(int(math.ceil(x / step) * step))
    return out


def exponential_gaps(n: int, mean: float) -> list[float]:
    """The n quantiles (i + 1/2)/n of an exponential with ``mean``."""
    return [-mean * math.log(1.0 - (i + 0.5) / n) for i in range(n)]


@dataclass
class Request:
    idx: int
    prompt: np.ndarray          # (S,) int32 token ids
    max_new: int                # tokens to generate
    due_s: float = 0.0          # when it is due, from t = 0
    group: int = -1             # shared-prefix group, -1 for none


def n_blocks(mix: dict, seconds: float = 0.0) -> int:
    if "blocks" in mix:
        return int(mix["blocks"])
    rate = mix["arrivals"]["rate_per_s"]
    return max(int(seconds * rate / mix["block"]), 1)


def _due(arrivals: dict, gaps: list[float]) -> list[float]:
    """Due times from gaps drawn at the process's rate while it is on."""
    on = np.cumsum(gaps).tolist()
    if arrivals["process"] != "on_off":
        return on
    burst, period = arrivals["burst"], arrivals["period_s"]
    span = period / burst                       # seconds on per period
    return [math.floor(t / span) * period + math.fmod(t, span) for t in on]


def requests(mix: dict, seed: int, vocab: int,
             seconds: float = 0.0) -> list[Request]:
    n = mix["block"]
    prompts = sizes(mix["prompt_tokens"], n)
    outputs = sizes(mix["output_tokens"], n)
    # one fixed pairing of prompt and output quantiles for every seed
    np.random.default_rng(0).shuffle(outputs)
    arrivals = mix["arrivals"]
    if arrivals["process"] == "backlog":
        gaps = [0.0] * n
    else:
        rate = arrivals["rate_per_s"] * (
            arrivals["burst"] if arrivals["process"] == "on_off" else 1.0)
        gaps = exponential_gaps(n, 1.0 / rate)
    rng = np.random.default_rng(seed)
    shared = mix.get("shared_prefix")
    prefixes = [rng.integers(0, vocab, shared["tokens"], dtype=np.int32)
                for _ in range(shared["groups"])] if shared else []
    rows = [(prompts[i], outputs[i], g)
            for _ in range(n_blocks(mix, seconds))
            for i, g in zip(rng.permutation(n), rng.permutation(gaps))]
    due = _due(arrivals, [g for *_, g in rows])
    out = []
    for i, ((S, m, _), t) in enumerate(zip(rows, due)):
        own = rng.integers(0, vocab, S, dtype=np.int32)
        g = i % len(prefixes) if prefixes else -1
        prompt = np.concatenate([prefixes[g], own]) if prefixes else own
        out.append(Request(i, prompt, max_new=int(m), due_s=float(t),
                           group=g))
    return out


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can send (what warm-up must cover)."""
    extra = mix.get("shared_prefix", {}).get("tokens", 0)
    return sorted({S + extra for S in sizes(mix["prompt_tokens"],
                                            mix["block"])})
