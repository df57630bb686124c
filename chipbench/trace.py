"""Reduction of a profiler trace to the benchmark's device numbers.

``load_dir`` reads the ``.xplane.pb`` a ``--trace 1`` run wrote (with
``jax.profiler.ProfileData``) into a plain structure:

    {"window": [start_ns, end_ns],          # the harness's host annotation
     "chips": {plane: {"ops": [[hlo_text, start_ns, dur_ns], ...],
                       "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[name, start_ns, dur_ns], ...]}

and the functions below reduce that structure, so a small recorded trace
checks them (``tests/data``). Every number is clipped to the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "chipbench/window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def from_profile(pd) -> dict:
    chips, host, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and "Core" not in \
                plane.name[len("/device:TPU:"):]:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events]
                elif line.name == MODULES_LINE:
                    mods += [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                             for ev in line.events]
            chips[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        window = [int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)]
                    elif ev.duration_ns > 0:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    if window is None:
        raise ValueError(f"trace has no {WINDOW!r} annotation")
    return {"window": window, "chips": chips, "host": host}


def load_dir(path) -> dict:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return from_profile(ProfileData.from_file(max(files, key=os.path.getmtime)))


def _clip(start, dur, window):
    a, b = max(start, window[0]), min(start + dur, window[1])
    return (a, b) if b > a else None


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(trace: dict) -> float:
    """Nanoseconds in the window in which an operation ran, averaged over
    the chips."""
    w = trace["window"]
    per = []
    for chip in trace["chips"].values():
        iv = [c for o in chip["ops"] if (c := _clip(o[1], o[2], w))]
        per.append(sum(b - a for a, b in _union(iv)))
    return sum(per) / len(per) if per else 0.0


def window_ns(trace: dict) -> int:
    return trace["window"][1] - trace["window"][0]


def op_ns(trace: dict, pattern: str) -> float:
    """Device time of the operations whose HLO text matches ``pattern``
    (a regular expression), summed over chips."""
    rx = re.compile(pattern)
    w = trace["window"]
    total = 0
    for chip in trace["chips"].values():
        for name, start, dur in chip["ops"]:
            c = rx.search(name) and _clip(start, dur, w)
            if c:
                total += c[1] - c[0]
    return float(total)


def module_runs(trace: dict, pattern: str) -> tuple[int, float]:
    """(runs, device ns) of the compiled programs whose name matches
    ``pattern`` and that started inside the window."""
    rx = re.compile(pattern)
    w = trace["window"]
    runs = [m for chip in trace["chips"].values() for m in chip["modules"]
            if rx.search(m[0]) and w[0] <= m[1] < w[1]]
    return len(runs), float(sum(m[2] for m in runs))


def op_label(text: str) -> str:
    """Short name of an HLO op event: ``%fusion.183 fusion`` out of the
    op's whole HLO line."""
    name = text.split(" = ", 1)[0]
    m = re.search(r"[})\]] ([a-z][a-z0-9_-]*)\(", text)
    return f"{name} {m.group(1)}" if m else name


def _self_times(ops: list, window) -> list:
    """[(op, self ns in the window)]: an op's time less the time of the
    ops nested inside it (a loop op spans its body's ops)."""
    out, stack = [], []                  # stack of [op, end, child ns]
    for op in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= op[1]:
            out.append(_close(stack.pop(), window))
        if stack:
            c = _clip(op[1], op[2], window)
            stack[-1][2] += c[1] - c[0] if c else 0
        stack.append([op, op[1] + op[2], 0])
    while stack:
        out.append(_close(stack.pop(), window))
    return out


def _close(entry, window):
    op, _, child = entry
    c = _clip(op[1], op[2], window)
    return op, max((c[1] - c[0] if c else 0) - child, 0)


def _module_at(modules: list, t: int) -> str:
    i = bisect.bisect_right(modules, [t, float("inf")]) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][0] + modules[i][1]:
        return modules[i][2]
    return ""


def top_ops(trace: dict, k: int = 10) -> list:
    """The ``k`` operations that took the most device self time in the
    window, each named ``<program>/<op> <kind>``: [[name, seconds], ...]."""
    w = trace["window"]
    tot: dict = {}
    for chip in trace["chips"].values():
        mods = sorted([m[1], m[2], m[0].split("(")[0]]
                      for m in chip["modules"])
        for op, ns in _self_times(chip["ops"], w):
            if ns:
                key = f"{_module_at(mods, op[1])}/{op_label(op[0])}"
                tot[key] = tot.get(key, 0) + ns
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, ns / 1e9] for n, ns in best]


def idle_gaps(trace: dict, k: int = 10) -> list:
    """The ``k`` longest stretches of the window with no operation on the
    first chip, each named by the host event that overlaps it most
    (events spanning the whole window excepted): [[name, seconds], ...]."""
    w = trace["window"]
    chip = next(iter(trace["chips"].values()), None)
    if chip is None:
        return []
    busy = _union([c for o in chip["ops"] if (c := _clip(o[1], o[2], w))])
    gaps, t = [], w[0]
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w[1]:
        gaps.append((t, w[1]))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:k]
    span = w[1] - w[0]
    host = [h for h in trace["host"] if h[2] < span]
    out = []
    for a, b in gaps:
        best, name = 0, "idle"
        for h_name, hs, hd in host:
            ov = min(b, hs + hd) - max(a, hs)
            if ov > best:
                best, name = ov, h_name
        out.append([name, (b - a) / 1e9])
    return out
