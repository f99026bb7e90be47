#!/usr/bin/env python3
"""The program's host spans in a traced run, and what they say of the
chip's idle time.

The program writes its spans (``repro.spans``: ``vp.*`` in the level
loop, ``dynbatch.*`` in the serving layer, ``host.gc`` for Python's
collector) as ``jax.profiler.TraceAnnotation`` host events into the same
``.xplane.pb`` as the device's programs, so they share the device trace's
clock.  :func:`collect` reads them, clipped to the ``bench.window`` event;
the functions below reduce them, with the device modules of
``trace.reduce`` (``summary["modules"]``), to the per-layer quantities:

* ``level_host_ms``: mean ``vp.level.host`` span, the host's work from a
  statvec to the return of the next step's dispatch;
* ``level_sync_lag_ms``: mean of ``vp.sync`` end minus the device end of
  the init or step module whose statvec it fetched (``clock_skew_min_ms``
  bounds how much of it is the two timelines' offset);
* ``row_fetch_ms``, ``wave_finish_ms``, ``wave_refill_ms``: mean
  ``vp.rows``, ``dynbatch.finish`` and ``dynbatch.cut`` spans;
* ``idle_unattributed_ms``: per wave, window time with no module running
  on the device and no leaf span (any span but the containers ``vp.wave``
  and ``dynbatch.execute``) open on any thread.

Run as a script it makes one traced run of a cell through
``run.run_cell``, keeps the spans, and prints one JSON line with these
quantities, the split of each kind of idle gap by span, the stalls (idle
gaps of 0.1 s or more) with the spans open in them, and the run's own
per-layer metrics::

    python3 bench/spans.py --workload <cell> --seed <n> --seconds <s> \\
        [--scale <k>] [--save <path.xplane.pb>]

``--scale`` generates the cell's graph at another scale (a small trace
for the tests); ``--save`` keeps a copy of the trace.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
PREFIXES = ("vp.", "dynbatch.", "host.")
WINDOW_EVENT = "bench.window"
CONTAINERS = ("vp.wave", "dynbatch.execute")
FETCHED = ("vp_init_state", "vp_push_step", "vp_pull_step")
STALL_S = 0.1


# -- reading ---------------------------------------------------------------

def collect(pd, window) -> list[tuple]:
    """The program's host events of a ``ProfileData`` inside ``window``
    (ns), clipped to it: ``(name, thread, start, end, args)``, sorted by
    start.  ``thread`` numbers the host lines, one per thread."""
    w0, w1 = window
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if not ev.name.startswith(PREFIXES):
                    continue
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if e > w0 and s < w1:
                    out.append((ev.name, i, max(s, w0), min(e, w1),
                                dict(ev.stats)))
    return sorted(out, key=lambda x: x[2])


def window_of(pd) -> tuple[float, float]:
    """The ``bench.window`` host event of a ``ProfileData`` (ns)."""
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_EVENT:
                        s = float(ev.start_ns)
                        return s, s + float(ev.duration_ns)
    raise ValueError(f"trace has no {WINDOW_EVENT!r} host event")


def read_file(path) -> tuple[list[tuple], tuple[float, float]]:
    """The spans of an ``.xplane.pb`` and its window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    window = window_of(pd)
    return collect(pd, window), window


# -- reducing --------------------------------------------------------------

def _durations(spans, name, need: str | None = None) -> list[float]:
    return [(e - s) / 1e9 for n, _, s, e, a in spans
            if n == name and (need is None or need in a)]


def _mean_ms(values) -> float | None:
    return 1000.0 * sum(values) / len(values) if values else None


def level_host_ms(spans) -> float | None:
    return _mean_ms(_durations(spans, "vp.level.host"))


def row_fetch_ms(spans) -> float | None:
    return _mean_ms(_durations(spans, "vp.rows"))


def wave_finish_ms(spans) -> float | None:
    return _mean_ms(_durations(spans, "dynbatch.finish"))


def wave_refill_ms(spans) -> float | None:
    # the cut a closing batcher waits in, with no wave, is not a refill
    return _mean_ms(_durations(spans, "dynbatch.cut", need="wave"))


def _paired(spans, modules, names) -> list[tuple]:
    """Each init or step module with the span of ``names`` that belongs to
    it, matched in order: every module has one statvec fetch and one
    dispatch.  Empty when the counts differ.  Order, not time, pairs them:
    the trace's device timeline sits a fraction of a millisecond early
    against the host's, so the next step can seem to start before the
    fetch that precedes it has returned."""
    mods = sorted((m for m in modules if m[0] in FETCHED),
                  key=lambda m: m[1])
    own = sorted((x for x in spans if x[0] in names), key=lambda x: x[2])
    return list(zip(own, mods)) if len(own) == len(mods) else []


def sync_lags(spans, modules) -> list[float]:
    """Seconds from the device end of the init or step module a
    ``vp.sync`` fetched the statvec of to the sync's return."""
    return [(sp[3] - m[2]) / 1e9
            for sp, m in _paired(spans, modules, ("vp.sync",))]


def clock_skew_min_ms(spans, modules) -> float | None:
    """A lower bound on how early the device timeline sits against the
    host's: the most any init or step module starts before the host span
    that dispatched it (``vp.init``, ``vp.level.host``) opened."""
    pairs = _paired(spans, modules, ("vp.init", "vp.level.host"))
    if not pairs:
        return None
    return max(max(sp[2] - m[1], 0.0) for sp, m in pairs) / 1e6


def level_sync_lag_ms(spans, modules) -> float | None:
    return _mean_ms(sync_lags(spans, modules))


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted unions of intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_intervals(modules, window) -> list[tuple[str, float, float]]:
    """The window's stretches with no module on the device, each named by
    the programs on either side (``window`` at the edges)."""
    w0, w1 = window
    out, t, prev = [], w0, "window"
    for name, s, e in sorted(modules, key=lambda m: m[1]):
        if s > t:
            out.append((f"{prev} -> {name}", t, s))
        if e > t:
            t, prev = e, name
    if w1 > t:
        out.append((f"{prev} -> window", t, w1))
    return out


def _leaves(spans) -> dict[str, list]:
    """The union of each leaf span's intervals, by name."""
    leaf = defaultdict(list)
    for n, _, s, e, _ in spans:
        if n not in CONTAINERS:
            leaf[n].append((s, e))
    return {n: _union(v) for n, v in leaf.items()}


def _split(s, e, leaves, covered) -> dict:
    """Seconds of the stretch [s, e) under each leaf span, and under none
    (``unattributed``)."""
    gap = [(s, e)]
    row = {"unattributed": (e - s - _overlap(gap, covered)) / 1e9}
    for n, iv in leaves.items():
        x = _overlap(gap, iv)
        if x:
            row[n] = x / 1e9
    return row


def idle_split(spans, modules, window) -> dict:
    """Seconds of device idle by kind of gap, and within each kind by the
    leaf span open across it (a stretch under two spans counts for both);
    ``unattributed`` is the part under no leaf span."""
    leaves = _leaves(spans)
    covered = _union(iv for v in leaves.values() for iv in v)
    out: dict = defaultdict(lambda: defaultdict(float))
    for kind, s, e in idle_intervals(modules, window):
        out[kind]["idle"] += (e - s) / 1e9
        for n, x in _split(s, e, leaves, covered).items():
            out[kind][n] += x
    return {k: dict(v) for k, v in out.items()}


def idle_s(modules, window) -> float:
    return sum(e - s for _, s, e in idle_intervals(modules, window)) / 1e9


def unattributed_s(spans, modules, window) -> float:
    return sum(r["unattributed"]
               for r in idle_split(spans, modules, window).values())


def idle_unattributed_ms(spans, modules, window) -> float | None:
    waves = wave_count(spans)
    if not waves:
        return None
    return 1000.0 * unattributed_s(spans, modules, window) / waves


def wave_count(spans) -> int:
    return sum(x[0] == "vp.wave" for x in spans)


def wave_steps(spans) -> list[list[str]]:
    """Per wave, ``mode:budget`` of each step it dispatched, re-runs
    included (``vp.level.host``), to set beside ``run.py``'s wave lines."""
    out = []
    for _, t, s, e, _ in sorted((x for x in spans if x[0] == "vp.wave"),
                                key=lambda x: x[2]):
        out.append([f"{a['mode']}:{a['budget']}"
                    for n, tt, ss, ee, a in spans
                    if n == "vp.level.host" and tt == t and s <= ss
                    and ee <= e])
    return out


def stalls(spans, modules, window, least_s: float = STALL_S) -> list[dict]:
    """Idle gaps of ``least_s`` or more, with the seconds each leaf span
    covers of them."""
    leaves = _leaves(spans)
    covered = _union(iv for v in leaves.values() for iv in v)
    out = []
    for kind, s, e in idle_intervals(modules, window):
        if (e - s) / 1e9 >= least_s:
            row = _split(s, e, leaves, covered)
            out.append({"gap": kind, "at_s": (s - window[0]) / 1e9,
                        "seconds": (e - s) / 1e9,
                        "spans": dict(sorted(row.items(),
                                             key=lambda kv: -kv[1]))})
    return out


def report(spans, modules, window) -> dict:
    """Every span quantity of one traced window: the spans of
    :func:`collect`, the device modules of ``trace.reduce`` and the
    window (ns)."""
    return {
        "waves": wave_count(spans),
        "level_host_ms": level_host_ms(spans),
        "level_sync_lag_ms": level_sync_lag_ms(spans, modules),
        "clock_skew_min_ms": clock_skew_min_ms(spans, modules),
        "row_fetch_ms": row_fetch_ms(spans),
        "wave_finish_ms": wave_finish_ms(spans),
        "wave_refill_ms": wave_refill_ms(spans),
        "idle_unattributed_ms": idle_unattributed_ms(spans, modules,
                                                     window),
        "program_idle_s": idle_s(modules, window),
        "idle_split_s": idle_split(spans, modules, window),
        "stalls": stalls(spans, modules, window),
        "wave_steps": wave_steps(spans),
        "span_counts": {n: sum(x[0] == n for x in spans)
                        for n in sorted({x[0] for x in spans})},
    }


# -- one traced run --------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scale", type=int, default=None)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    import run
    import trace as bench_trace
    from jax.profiler import ProfileData

    kept: dict = {}
    reduce_dir = bench_trace.reduce_dir

    def reduce_dir_with_spans(trace_dir):
        summary = reduce_dir(trace_dir)
        path = max(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
        pd = ProfileData.from_file(str(path))
        window = window_of(pd)
        kept.update(spans=collect(pd, window), modules=summary["modules"],
                    window=window)
        if args.save:
            shutil.copy(path, args.save)
        return summary

    bench_trace.reduce_dir = reduce_dir_with_spans
    manifest = run.load_manifest()
    cell, cfg, traffic = run.cell_inputs(manifest, args.workload)
    if args.scale is not None:
        cfg = dict(cfg, scale=args.scale, name=f"{cfg['name']}-s{args.scale}")
    metrics = run.cell_metrics(manifest, args.workload, True)
    lines: list[str] = []

    def log(*a):
        lines.append(" ".join(map(str, a)))
        print(*a, file=sys.stderr, flush=True)

    out = run.run_cell(cell, cfg, traffic, metrics, args.seed, args.seconds,
                       True, log=log)
    result = {"correct": out["correct"], "metrics": out["metrics"],
              "device": out["device"], "spans": report(**kept),
              "wave_lines": [ln for ln in lines if ln.startswith("wave ")]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
