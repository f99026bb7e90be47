"""Reduce a JAX profiler trace of one run's window to per-layer numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  The window is the
host event ``bench.window`` that ``run.py`` opens around the load and its
drain.  On each TPU plane (``/device:TPU:<i>``) the line ``XLA Modules``
holds one event per program execution, named after the jitted function
(``jit_vp_push_step(<id>)``), and ``XLA Ops`` one event per operation.

* busy: the union of the op intervals inside the window, averaged over
  the devices; idle share is 1 - busy / window;
* program time: module durations summed by program name;
* step gaps: inside a wave, the time from the end of the init or a step
  program to the start of the next step program, which is what one level
  costs the host;
* breakdown: the ten operations (``program/op``) with the most device time
  and the ten kinds of idle gap (named by the programs on either side)
  with the most idle time.
"""
from __future__ import annotations

import re
from collections import defaultdict
from pathlib import Path

WINDOW_EVENT = "bench.window"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
INIT, STEPS = "vp_init_state", ("vp_push_step", "vp_pull_step")


def program_name(event_name: str) -> str:
    """``jit_vp_push_step(1234)`` -> ``vp_push_step``."""
    name = re.sub(r"\(\d+\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def union_length(intervals) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e > end:
            total += e - max(s, end)
            end = e
    return total


def read_xplane(path) -> dict:
    """The window and, per TPU plane, its module and op events (ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    window = None
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            mods, ops = [], []
            for line in plane.lines:
                if line.name not in (MODULE_LINE, OP_LINE):
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    item = (ev.name, s, s + float(ev.duration_ns))
                    (mods if line.name == MODULE_LINE else ops).append(item)
            devices.append({"modules": sorted(mods, key=lambda m: m[1]),
                            "ops": ops})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_EVENT:
                        s = float(ev.start_ns)
                        window = (s, s + float(ev.duration_ns))
    return {"window": window, "devices": devices}


def op_label(hlo: str) -> str:
    """``%fusion.48 = s32[8388608]{0:T(1024)} fusion(...)`` ->
    ``%fusion.48 s32[8388608]``: the op and the shape it writes."""
    head, _, rest = hlo.partition(" = ")
    shape = re.match(r"[^{ (]*", rest).group(0) if rest else ""
    return f"{head} {shape}".strip()


def op_module(ops, modules):
    """Pair each op with the module running around it (sorted sweep)."""
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j + 1 < len(modules) and modules[j + 1][1] <= s:
            j += 1
        mod = (program_name(modules[j][0])
               if modules and modules[j][1] <= s <= modules[j][2] else "?")
        out.append((f"{mod}/{op_label(name)}", s, e))
    return out


def reduce(raw: dict) -> dict:
    """Per-layer quantities of one trace (seconds unless named)."""
    if raw["window"] is None:
        raise ValueError(f"trace has no {WINDOW_EVENT!r} host event")
    if not raw["devices"]:
        raise ValueError("trace has no TPU device plane")
    w0, w1 = raw["window"]

    def clip(items):
        return [(n, max(s, w0), min(e, w1)) for n, s, e in items
                if e > w0 and s < w1]

    busy = []
    program_s: dict[str, float] = defaultdict(float)
    op_s: dict[str, float] = defaultdict(float)
    gap_s: dict[str, float] = defaultdict(float)
    modules0 = None
    for dev in raw["devices"]:
        mods, ops = clip(dev["modules"]), clip(dev["ops"])
        if modules0 is None:
            modules0 = [(program_name(n), s, e) for n, s, e in mods]
        busy.append(union_length((s, e) for _, s, e in ops))
        for n, s, e in mods:
            program_s[program_name(n)] += (e - s) / 1e9
        for n, s, e in op_module(ops, mods):
            op_s[n] += (e - s) / 1e9
        for (a, _, a_end), (b, b_start, _) in zip(mods, mods[1:]):
            if b_start > a_end:
                gap_s[f"{program_name(a)} -> {program_name(b)}"] += (
                    (b_start - a_end) / 1e9)
    ndev = len(raw["devices"])

    def top(d):
        return [[k, v / ndev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / ndev / 1e9,
        "program_s": {k: v / ndev for k, v in program_s.items()},
        "modules": modules0,
        "breakdown": {"device_ops": top(op_s), "idle_gaps": top(gap_s)},
    }


def reduce_dir(trace_dir) -> dict:
    """Reduce the newest ``.xplane.pb`` under a profiler output directory."""
    paths = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return reduce(read_xplane(paths[-1]))


def program_seconds(summary: dict, name: str) -> float | None:
    """Device seconds of one program in the window, or None if it never
    ran there."""
    return summary["program_s"].get(name)


def step_gaps(modules) -> list[float]:
    """Seconds from the end of an init or step program to the start of the
    next step program, for each such consecutive pair (one per level)."""
    out = []
    for (a, _, a_end), (b, b_start, _) in zip(modules, modules[1:]):
        if b in STEPS and (a in STEPS or a == INIT):
            out.append(max(b_start - a_end, 0.0) / 1e9)
    return out
