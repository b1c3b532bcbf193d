"""Reduction of a JAX profiler trace to the benchmark's device numbers.

``load(logdir)`` reads the newest ``*.xplane.pb`` under ``logdir`` into
plain tuples; ``reduce(...)`` turns them into

* ``window_s``  length of the traced window (the host annotation
  ``bench.window``);
* ``busy_s``    union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices that ran any;
* ``device_ops`` seconds per operation name, most first;
* ``modules``   seconds per compiled program (``XLA Modules`` line);
* ``idle_gaps`` idle device seconds by what the host was doing: the
  innermost host interval (a ``bench.*`` annotation, or a program span
  mapped onto the trace clock) open at each gap's midpoint.
"""
from __future__ import annotations

import re
from pathlib import Path

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(logdir) -> dict:
    """``{plane: {line: [(name, start_ns, end_ns), ...]}}`` of the newest
    trace under ``logdir``."""
    import jax

    paths = sorted(Path(logdir).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(str(paths[-1]))
    planes = {}
    for plane in data.planes:
        lines = {}
        for line in plane.lines:
            evs = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in line.events]
            lines.setdefault(line.name, []).extend(evs)
        planes[plane.name] = lines
    return planes


def is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:[A-Z]+:\d+", name) is not None


def union(intervals) -> list:
    """Sorted, merged ``[(start, end)]`` of possibly overlapping ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi) -> list:
    """Complement of the merged ``busy`` intervals inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def window_of(planes) -> tuple:
    for lines in planes.values():
        for evs in lines.values():
            for name, s, e in evs:
                if name == WINDOW:
                    return s, e
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def host_intervals(planes) -> list:
    """``bench.*`` annotations on any host plane, as (name, start, end)."""
    return [ev for pname, lines in planes.items()
            if not is_device_plane(pname)
            for evs in lines.values() for ev in evs
            if ev[0].startswith("bench.") and ev[0] != WINDOW]


def _label(mid, intervals) -> str:
    best = None
    for name, s, e in intervals:
        if s <= mid < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else WINDOW


def _strip(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def op_name(hlo: str) -> str:
    """An ``XLA Ops`` event's HLO text shortened to instruction, opcode
    and result shape: ``fusion.102 fusion pred[7000000]``."""
    if " = " not in hlo:
        return hlo
    lhs, rhs = hlo.split(" = ", 1)
    m = re.search(r"\s([a-z][\w-]*)\(", " " + rhs)
    shape = "tuple" if rhs.startswith("(") else re.split(r"[{ ]", rhs)[0]
    return f"{lhs.lstrip('%')} {m.group(1) if m else '?'} {shape}"


def reduce(planes, host=(), device_lines=None, top: int = 10) -> dict:
    """Device numbers over the ``bench.window`` annotation.

    ``host`` adds (name, start_ns, end_ns) intervals on the trace clock
    to the ``bench.*`` annotations for labelling idle gaps.
    ``device_lines(plane, line) -> bool`` picks the lines whose events
    are device operations (default: the ``XLA Ops`` line of each
    ``/device:<KIND>:<n>`` plane).
    """
    if device_lines is None:
        def device_lines(plane, line):
            return is_device_plane(plane) and line == OPS_LINE
    lo, hi = window_of(planes)
    ops, modules, busy_each, idle = {}, {}, [], {}
    intervals = host_intervals(planes) + list(host)
    for pname, lines in planes.items():
        plane_ops = []
        for lname, evs in lines.items():
            if device_lines(pname, lname):
                for name, s, e in evs:
                    if e > lo and s < hi:
                        plane_ops.append((s, e))
                        key = op_name(name)
                        ops[key] = ops.get(key, 0.0) \
                            + (min(e, hi) - max(s, lo)) * 1e-9
            elif lname == MODULES_LINE and is_device_plane(pname):
                for name, s, e in clip_named(evs, lo, hi):
                    key = _strip(name)
                    modules[key] = modules.get(key, 0.0) + (e - s) * 1e-9
        if not plane_ops:
            continue
        busy = union(clip(plane_ops, lo, hi))
        busy_each.append(sum(e - s for s, e in busy) * 1e-9)
        for s, e in gaps(busy, lo, hi):
            key = _label((s + e) // 2, intervals)
            idle[key] = idle.get(key, 0.0) + (e - s) * 1e-9
    n = max(1, len(busy_each))
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_each) / n,
        "devices": len(busy_each),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "modules": modules,
        "idle_gaps": sorted(((k, v / n) for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def clip_named(evs, lo, hi) -> list:
    return [(name, max(s, lo), min(e, hi)) for name, s, e in evs
            if e > lo and s < hi]
