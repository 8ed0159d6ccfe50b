"""Reduction of a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a small
dict (and ``save``/``read`` keep that dict as JSON, which the tests check
on a recorded trace):

  {"device": [{"plane": "/device:TPU:0",
               "ops": [[name, start_ns, dur_ns], ...],
               "modules": [[name, start_ns, dur_ns], ...]}, ...],
   "host": [[name, start_ns, dur_ns], ...]}

``ops`` are the events of the device's op line ("XLA Ops"), ``modules``
those of its program line ("XLA Modules"), ``host`` every event of the
host threads (where ``jax.profiler.TraceAnnotation`` spans land).  All
times share the trace's clock.
"""
from __future__ import annotations

import glob
import json
import os

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
# the engine's segment program, as its executions are named on the
# module line
SEGMENT_MODULE = "run_segment"


def load(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device": [], "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"plane": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OP_LINE: "ops", MODULE_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
            out["device"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend([e.name, float(e.start_ns),
                                    float(e.duration_ns)]
                                   for e in line.events)
    return out


def save(tr: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(tr, f)


def read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def span(tr: dict, name: str) -> tuple:
    """(start_ns, end_ns) of the last host span of this name."""
    hits = [e for e in tr["host"] if e[0] == name]
    if not hits:
        raise KeyError(f"no host span {name!r} in the trace")
    e = max(hits, key=lambda e: e[1])
    return e[1], e[1] + e[2]


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ")[0].lstrip("%")


def leaves(events) -> list:
    """The events that hold no other event: the op line nests a loop or
    a conditional around the ops it runs."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    leaf = [True] * len(ev)
    stack = []
    for i, (_, s, _d) in enumerate(ev):
        while stack and ev[stack[-1]][1] + ev[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            leaf[stack[-1]] = False
        stack.append(i)
    return [e for e, keep in zip(ev, leaf) if keep]


def stretch(tr: dict, name: str) -> dict:
    """The traced stretch, cut to whole executions of the segment program
    inside the host span ``name`` (the first and the last are left out:
    the profiler may have started or stopped in the middle of them).
    Returns its bounds, its leaf ops, the device's busy time and the
    number of whole segments."""
    a, b = span(tr, name)
    devs = [d for d in tr["device"] if d["ops"]]
    mods = sorted(m for d in devs[:1] for m in d["modules"]
                  if SEGMENT_MODULE in m[0] and a <= m[1]
                  and m[1] + m[2] <= b)[1:-1]
    if mods:
        a, b = mods[0][1], mods[-1][1] + mods[-1][2]
    ops = clip(leaves(devs[0]["ops"]), a, b) if devs else []
    busy = [busy_ns(leaves(d["ops"]), a, b) for d in devs]
    return {"a": a, "b": b, "ops": ops, "segments": len(mods),
            "busy_s": (sum(busy) / len(busy) if busy else 0.0) * 1e-9,
            "window_s": (b - a) * 1e-9}


def clip(events, a: float, b: float) -> list:
    """Events cut to [a, b] (those wholly outside dropped)."""
    out = []
    for name, s, d in events:
        s2, e2 = max(s, a), min(s + d, b)
        if e2 > s2:
            out.append((name, s2, e2 - s2))
    return out


def union(events) -> list:
    """Disjoint [start, end] intervals covered by the events."""
    iv = sorted((s, s + d) for _, s, d in events)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(events, a: float, b: float) -> float:
    return sum(e - s for s, e in union(clip(events, a, b)))


def idle_gaps(events, a: float, b: float, host, names, top: int = 10):
    """The ``top`` longest stretches of [a, b] with no device op, each
    named by the innermost of the host spans ``names`` open at its
    midpoint."""
    gaps, t = [], a
    for s, e in union(clip(events, a, b)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if b > t:
        gaps.append((t, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [h for h in host if h[0] in names]
    out = []
    for s, e in gaps[:top]:
        mid = 0.5 * (s + e)
        open_ = [h for h in spans if h[1] <= mid <= h[1] + h[2]]
        label = min(open_, key=lambda h: h[2])[0] if open_ else "(none)"
        out.append([label, (e - s) * 1e-9])
    return out


def op_totals(events, top: int = 10) -> list:
    """[[op, seconds], ...] of the ops (numbered instances summed) with
    the most device time."""
    tot: dict = {}
    for name, _, d in events:
        key = op_name(name).rstrip("0123456789").rstrip(".")
        tot[key] = tot.get(key, 0.0) + d
    rows = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v * 1e-9] for k, v in rows]
