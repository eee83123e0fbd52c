"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: the traced window, the device's busy time (the union of the
intervals in which an XLA op ran), device time per op and per program
(XLA module), and the ``breakdown`` of the result line.

The window is the benchmark's own ``bench_window`` annotation on the host.
Device planes are ``/device:<KIND>:<n>``; busy time and op time come from
their ``XLA Ops`` line, program time from their ``XLA Modules`` line.  A
control-flow op (``while``, ``conditional``, ``call``) spans the ops it
runs, and a host transfer waits on the host: neither counts as work.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

WINDOW = "bench_window"
_DEVICE = re.compile(r"^/device:[A-Z]+:\d+$")
#: ops that are not work of their own: control flow that spans the ops
#: it runs, and transfers that wait for the host
_NOT_WORK = re.compile(r"[)}\]] (while|conditional|call)\(|is_host_transfer=true")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals):
    """Sorted, merged copy of ``[(start, end), ...]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def _module_name(name: str) -> str:
    """``jit_chunk(12)`` → ``jit_chunk``."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(path: str) -> dict:
    """Reduce one trace file.  Times are seconds; ``ops`` and ``modules``
    map a name to its summed device time inside the window, averaged over
    the devices; ``busy`` is the merged busy intervals of each device."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host_events = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, d = ev.start_ns, ev.duration_ns
                    if ev.name == WINDOW:
                        window = (s, s + d)
                    elif d > 0:
                        host_events.append((s, s + d, ev.name, line.name))
        elif _DEVICE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in (lines["XLA Ops"].events
                              if "XLA Ops" in lines else ())]
            mods = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in (lines["XLA Modules"].events
                               if "XLA Modules" in lines else ())]
            devices.append((plane.name, ops, mods))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation on the host")
    lo, hi = window
    n_dev = max(len(devices), 1)
    op_time = defaultdict(float)
    mod_time = defaultdict(float)
    mod_count = defaultdict(int)
    busy_total = 0.0
    busy = []
    for _, ops, mods in devices:
        inside = []
        for s, e, name in ops:
            s, e = _clip(s, e, lo, hi)
            if e > s and not _NOT_WORK.search(name):
                inside.append((s, e))
                op_time[name] += (e - s) / 1e9 / n_dev
        merged = _union(inside)
        busy.append(merged)
        busy_total += sum(e - s for s, e in merged) / 1e9
        for s, e, name in mods:
            s, e = _clip(s, e, lo, hi)
            if e > s:
                mod_time[_module_name(name)] += (e - s) / 1e9 / n_dev
                mod_count[_module_name(name)] += 1
    return {"window_s": (hi - lo) / 1e9, "window_ns": (lo, hi),
            "n_devices": len(devices),
            "busy_s": busy_total / n_dev,
            "ops": dict(op_time), "modules": dict(mod_time),
            "module_events": dict(mod_count),
            "busy": busy, "host": host_events}


def time_matching(table: dict, pattern: str) -> float:
    """Summed time of the entries whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(t for name, t in table.items() if rx.search(name))


def idle_gaps(red: dict, top: int = 10):
    """The longest idle gaps of device 0 inside the window, each named by
    the innermost host event that spans its midpoint (``unattributed``
    where none does)."""
    if not red["busy"]:
        return []
    lo, hi = red["window_ns"]
    edges = [lo] + [x for iv in red["busy"][0] for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        spans = [(he - hs, name) for hs, he, name, _ in red["host"]
                 if hs <= mid <= he]
        label = min(spans)[1] if spans else "unattributed"
        out.append([label, (e - s) / 1e9])
    return out


def short_name(op: str, width: int = 160) -> str:
    """``%fusion.12 = bf16[8,1024]{...} fusion(...)`` → the name, the opcode
    and the result shape, without layouts."""
    m = re.match(r"%(\S+) = (.*)", op)
    if not m:
        return op[:width]
    name, rest = m.groups()
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    return f"{name} {rest}"[:width]


def breakdown(red: dict, top: int = 10) -> dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[short_name(k), v] for k, v in ops],
            "idle_gaps": idle_gaps(red, top)}
