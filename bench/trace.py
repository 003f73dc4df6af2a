"""From a profiler trace (``.xplane.pb``) to device metrics.

Busy time is the union of the intervals in which an operation ran on a
device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged over
the devices; the window is the traced span of the benchmark's own host spans
and the device work. Idle gaps between busy intervals are named by the
innermost host span (``bench.*``) that covers their middle, so the breakdown
says what the host was doing while the device waited.

A trace recorded on the CPU has no device plane; there the operations are
the host events that carry an ``hlo_op`` statistic, so the same reduction can
be tested without a chip.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]          # (start_s, end_s)
SPAN_PREFIX = "bench."
TOP = 10


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals; the result is sorted and disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of [lo, hi] between the merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def name_gap(gap: Interval, spans: Sequence[Tuple[str, float, float]]) -> str:
    """The innermost host span covering the gap's middle, else 'no span'."""
    mid = (gap[0] + gap[1]) / 2
    cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
    return min(cover)[1] if cover else "no span"


def _stats(ev) -> dict:
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            return {k: v for k, v in ev.stats}
        except (TypeError, ValueError):
            return {}


def _modules_of_ops(plane) -> list:
    """Where a device plane has no ``XLA Modules`` line: its operations,
    named by the ``hlo_module`` statistic they carry."""
    out = []
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            mod = _stats(ev).get("hlo_module")
            if mod:
                out.append((str(mod), ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9))
    return out


def events(path: Path) -> dict:
    """Device ops and modules per device, and the benchmark's host spans."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    devices: Dict[str, dict] = {}
    spans, cpu_ops = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    d[key] += [(ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in line.events]
            if d["ops"] and not d["modules"]:
                d["modules"] = _modules_of_ops(plane)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns * 1e-9,
                          (ev.start_ns + ev.duration_ns) * 1e-9)
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, *iv))
                    elif "hlo_op" in _stats(ev) and ev.duration_ns > 0:
                        cpu_ops.append((ev.name, *iv))
    if not devices and cpu_ops:
        devices["/host:CPU"] = {"ops": cpu_ops, "modules": []}
    return {"devices": devices, "spans": spans}


def clip(events, lo: float, hi: float) -> list:
    """Named intervals cut to [lo, hi]; those outside it dropped."""
    out = [(n, max(s, lo), min(e, hi)) for n, s, e in events]
    return [(n, s, e) for n, s, e in out if e > s]


def reduce_events(ev: dict, clip_s: Optional[float] = None) -> Optional[dict]:
    """Busy and idle seconds, per-module device time, top operations and the
    longest named idle gaps; None when no operation ran on a device. With
    ``clip_s`` only the first ``clip_s`` seconds of the trace count."""
    devs = {k: v for k, v in ev["devices"].items() if v["ops"]}
    if not devs:
        return None
    spans = ev["spans"]
    points = [t for d in devs.values() for _, s, e in d["ops"] for t in (s, e)]
    points += [t for _, s, e in spans for t in (s, e)]
    lo, hi = min(points), max(points)
    if clip_s is not None and hi > lo + clip_s:
        hi = lo + clip_s
        spans = clip(spans, lo, hi)
        devs = {k: {key: clip(v[key], lo, hi) for key in ("ops", "modules")}
                for k, v in devs.items()}
    busy, op_time, mod_time, idle = [], defaultdict(float), defaultdict(float), []
    for d in devs.values():
        merged = union([(s, e) for _, s, e in d["ops"]])
        busy.append(sum(e - s for s, e in merged))
        for n, s, e in d["ops"]:
            op_time[n] += e - s
        for n, s, e in d["modules"]:
            mod_time[n] += e - s
        idle += [(name_gap(g, spans), g[1] - g[0]) for g in gaps(merged, lo, hi)]
    busy_s = sum(busy) / len(busy)
    window = hi - lo
    return {
        "busy_s": busy_s, "window_s": window,
        "idle_share": 100.0 * (1.0 - busy_s / window),
        "modules": dict(mod_time),
        "breakdown": {
            "device_ops": sorted(op_time.items(), key=lambda x: -x[1])[:TOP],
            "idle_gaps": sorted(idle, key=lambda x: -x[1])[:TOP]},
    }


def module_seconds(modules: Dict[str, float], part: str) -> float:
    return sum(t for n, t in modules.items() if part in n)


def reduce_dir(trace_dir: Path, *, shapes, peak: dict,
               decode_calls: Sequence[Tuple[Sequence[int], dict]],
               flops: float, clip_s: Optional[float] = None) -> Optional[dict]:
    """Reduce the one trace under ``trace_dir`` (its first ``clip_s``
    seconds, where given) and add the model-step shares: the decode program
    against its roofline, and the traced work's FLOPs against the chip's
    peak over the window. ``decode_calls`` holds each traced decode call's
    (contexts, counts), which the architecture's ``shapes.decode`` takes."""
    from bench.roofline import roofline_seconds
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    red = reduce_events(events(files[-1]), clip_s)
    if red is None:
        return None
    dev_decode = module_seconds(red["modules"], "decode_paged")
    need = [roofline_seconds(**_fb(shapes.decode(c, n)), peak=peak)
            for c, n in decode_calls if c]
    if dev_decode > 0 and need:
        red["decode_roofline"] = 100.0 * sum(t for t, _ in need) / dev_decode
        bounds = {b for _, b in need}
        red["decode_bound"] = bounds.pop() if len(bounds) == 1 else "mixed"
    if flops > 0:
        red["mfu"] = 100.0 * flops / (red["window_s"] * peak["flops"])
    return red


def _fb(work: dict) -> dict:
    return {"flops": work["flops"], "nbytes": work["bytes"]}
