"""Roofline analysis from the compiled dry-run artifact.

Three terms per (arch x shape x mesh), in seconds, priced at the peaks of
``core.costmodel.TARGET_KIND`` from the one table of published rates:

  compute    = FLOPs / (chips x peak FLOP/s)
  memory     = HBM bytes / (chips x HBM bytes/s)
  collective = collective bytes / (chips x ICI bytes/s per link)

FLOPs/bytes come from two sources that are cross-checked:
  * ``compiled.cost_analysis()`` — exact for straight-line HLO, but counts
    a ``while`` body ONCE; our models scan over layers, so loop bodies are
    trip-corrected by walking the HLO call graph (see ``_walk``).
  * the analytic model (``core.costmodel`` conventions) — 6*N*D for train,
    2*N_active per token for inference.

Collective bytes are NOT in cost_analysis: we parse the post-SPMD HLO text,
sum the shard-local result bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute (all-reduce weighted 2x for
ring reduce+broadcast traffic), trip-correcting loop bodies the same way.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field

from repro.core.costmodel import TARGET

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_COLL_FACTOR = {"all-reduce": 2.0}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_ENTRY_RE = re.compile(r"^ENTRY\s+%?([\w\.\-]+)")
_WHILE_RE = re.compile(r"while\(.*body=%?([\w\.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls)=%?([\w\.\-]+)")
_FUSION_SKIP = ("fused_computation", "region")


def _shape_bytes(text: str) -> int:
    """Total bytes of the first (possibly tuple) shape in ``text``."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class HloStats:
    collective_bytes: float = 0.0
    per_op: dict = field(default_factory=dict)
    n_collectives: int = 0
    n_while: int = 0
    dot_flops: float = 0.0               # trip-corrected matmul FLOPs
    n_dots: int = 0


_RESULT_RE = re.compile(r"^\s*%?([\w\.\-]+)\s*=\s*(\w+\[[\d,]*\])")
_DOT_LHS_RE = re.compile(r"\bdot\(\s*(?:(\w+\[[\d,]*\])[^%,]*)?%?([\w\.\-]+)")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _dims(shape_text: str) -> list:
    m = _SHAPE_RE.search(shape_text)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _symbol_shapes(hlo_text: str) -> dict:
    """name -> dims for every op result in the module (operands in HLO text
    are bare %name references, so dot FLOPs need this table)."""
    table = {}
    for line in hlo_text.splitlines():
        m = _RESULT_RE.match(line)
        if m:
            table[m.group(1)] = _dims(m.group(2))
    return table


def _dot_flops(line: str, symbols: dict) -> float:
    """2 * prod(result dims) * prod(lhs contracting dims)."""
    rm = _RESULT_RE.match(line)
    dm = _DOT_LHS_RE.search(line)
    cm = _LHS_CONTRACT_RE.search(line)
    if not (rm and dm):
        return 0.0
    res = _dims(rm.group(2))
    lhs = _dims(dm.group(1)) if dm.group(1) else symbols.get(dm.group(2), [])
    contract = 1
    if cm and cm.group(1) and lhs:
        for i in cm.group(1).split(","):
            idx = int(i)
            if idx < len(lhs):
                contract *= lhs[idx]
    n = 1
    for d in res:
        n *= d
    return 2.0 * n * contract


def parse_hlo(hlo_text: str, *, loop_trips=1) -> HloStats:
    """Walk the HLO module, trip-correcting loop-body ops.

    ``loop_trips``: int (single loop class — the layer scan) or a list of
    per-jax-scan-level trip counts outermost-first (e.g. [microbatches,
    layers] for gradient-accumulated training).

    Each op's multiplier comes from its own op_name metadata: JAX records
    one "while/body" path element per scan level, which survives XLA's
    wide-scan splitting (a single jax scan may lower to several nested
    HLO whiles — structural nesting therefore over/under-counts; metadata
    doesn't). Ops without metadata fall back to the structural in-loop
    flag with the full trip product."""
    trips = list(loop_trips) if isinstance(loop_trips, (list, tuple)) \
        else [loop_trips]
    # split into computations: headers are top-level "name (params) -> T {"
    comps: dict[str, list[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        if cur is None and line.rstrip().endswith("{"):
            m = _COMP_RE.match(line)
            if m and "=" not in line.split("(")[0]:
                cur = m.group(1)
                comps[cur] = []
                em = _ENTRY_RE.match(line)
                if em:
                    entry = em.group(1)
                continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is not None:
            comps[cur].append(line)
    if entry is None:
        entry = next(iter(comps), None)

    stats = HloStats()
    symbols = _symbol_shapes(hlo_text)
    visited_stack: list[str] = []
    full_product = 1.0
    for t in trips:
        full_product *= t

    def meta_mult(ls: str, in_loop: bool) -> float:
        n = ls.count("/while/")
        if n == 0:
            return full_product if in_loop else 1.0
        m = 1.0
        for t in trips[:n]:
            m *= t
        if n > len(trips):               # deeper than known scan levels:
            pass                         # cap at the full product
        return m

    def walk(comp: str, in_loop: bool):
        if comp not in comps or comp in visited_stack:
            return
        visited_stack.append(comp)
        for line in comps[comp]:
            ls = line.strip()
            mult = meta_mult(ls, in_loop)
            op = None
            for c in COLLECTIVES:
                # match the op name, e.g. "= bf16[...] all-gather("
                if f" {c}(" in ls or f" {c}-start(" in ls:
                    op = c
                    break
            if op is not None:
                rhs = ls.split("=", 1)[-1]
                b = _shape_bytes(rhs.split(op)[0]) * _COLL_FACTOR.get(op, 1.0)
                stats.collective_bytes += b * mult
                stats.per_op[op] = stats.per_op.get(op, 0.0) + b * mult
                stats.n_collectives += 1
            if " dot(" in ls:
                stats.dot_flops += _dot_flops(ls, symbols) * mult
                stats.n_dots += 1
            wm = _WHILE_RE.search(ls)
            if wm:
                stats.n_while += 1
                walk(wm.group(1), True)
                continue
            cm = _CALL_RE.search(ls)
            if cm and cm.group(1) in comps:
                walk(cm.group(1), in_loop)
        visited_stack.pop()

    if entry:
        walk(entry, False)
    return stats


# ---------------------------------------------------------------------------
# Analytic FLOPs/bytes (model-level; cross-check for cost_analysis)
# ---------------------------------------------------------------------------

def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS: 6*N*D for training, 2*N_active*D_tok for inference."""
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n_active * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.global_batch * shape.seq_len
    return 2.0 * n_active * shape.global_batch          # one token


def hbm_bytes_estimate(cfg, shape) -> float:
    """First-order HBM traffic: params once + KV/state traffic."""
    pbytes = cfg.n_params() * 2.0
    if shape.kind == "train":
        return pbytes * 3 * 2                            # p+g+opt r/w
    if shape.kind == "decode":
        kv = 0.0
        if cfg.family != "ssm":
            sc = min(shape.seq_len, cfg.sliding_window) if cfg.sliding_window \
                else shape.seq_len
            kv_bytes = 1 + 4.0 / cfg.head_dim_ \
                if cfg.kv_cache_dtype == "int8" else 2
            kv = (cfg.n_layers * shape.global_batch * sc
                  * cfg.n_kv_heads * cfg.head_dim_ * 2 * kv_bytes)
        if cfg.family == "ssm":
            hd = cfg.rwkv.head_dim
            kv = cfg.n_layers * shape.global_batch \
                * (cfg.d_model // hd) * hd * hd * 4 * 2
        return pbytes + kv
    return pbytes


@dataclass
class Roofline:
    chips: int
    flops: float
    hbm_bytes: float
    collective_bytes: float
    model_flops_: float

    @property
    def compute_s(self) -> float:
        return self.flops / (self.chips * TARGET.flops)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / (self.chips * TARGET.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.chips * TARGET.ici_bw)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        return self.model_flops_ / max(self.flops, 1.0)

    def to_dict(self) -> dict:
        return {
            "chips": self.chips, "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "model_flops": self.model_flops_,
            "useful_ratio": self.useful_ratio,
        }
