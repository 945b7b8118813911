"""Reduce a ``jax.profiler`` trace (``*.xplane.pb``) to what the per-layer
metrics read: device busy time, time per operation and per executable, and
the longest idle gaps with the host events that overlap them.

Busy time is the union of the intervals in which any operation ran on a
device ("XLA Ops" line of each ``/device:`` plane), averaged over the
devices. An executable's time is the sum of its runs on the "XLA Modules"
line; an operation's, the sum of its events, by the name the trace gives
(the HLO instruction, e.g. ``paged_flash_decode.3``), so a kernel is found
by its name prefix.

Every device time is clipped to the traced window: the host event named
``WINDOW``, which the harness opens once the profiler has started and closes
before it stops, on the trace's own clock. So busy time, operations and
executables cover the same interval as the tokens the readers count, and
not the device work recorded while the profiler was starting or stopping.
A trace without that mark is read whole, from its first device operation
to its last.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "chipbench.window"
_SUFFIX = re.compile(r"(\.\d+)+$|\(\d+\)$")


def base_name(name: str) -> str:
    """The instruction or executable an event names, without its numbering:
    ``%fusion.12 = f32[8]{0} fusion(...)`` → ``fusion``;
    ``jit__decode_fn(3)`` → ``jit__decode_fn``."""
    return _SUFFIX.sub("", name.split(" = ", 1)[0].strip().lstrip("%"))


def latest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return found[-1] if found else None


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _events(line):
    for ev in line.events:
        s = int(ev.start_ns)
        yield ev.name, s, s + int(ev.duration_ns)


def _clipped(line, lo: float, hi: float):
    for name, s, e in _events(line):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield name, s, e


def reduce_planes(planes) -> Dict:
    """The reduction of parsed planes (``ProfileData.planes``). Returns
    seconds throughout; ``devices`` is 0 when no device plane was found,
    ``marked`` whether the window's mark was."""
    host: List[Tuple[str, int, int]] = []
    devices = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
        elif plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices.append(lines)
    marks = [(s, e) for name, s, e in host if name == WINDOW]
    host = [h for h in host if h[0] != WINDOW]
    if marks:
        lo, hi = marks[0]
    else:
        spans = [(s, e) for lines in devices
                 for _, s, e in _events(lines[OPS_LINE])]
        lo, hi = (min(s for s, _ in spans), max(e for _, e in spans)) \
            if spans else (0, 0)
    busy_ns = 0
    ops: Dict[str, float] = collections.defaultdict(float)
    op_calls: Dict[str, int] = collections.defaultdict(int)
    modules: Dict[str, float] = collections.defaultdict(float)
    module_runs: Dict[str, int] = collections.defaultdict(int)
    gaps: List[Tuple[int, int]] = []
    for i, lines in enumerate(devices):
        intervals = []
        for name, s, e in _clipped(lines[OPS_LINE], lo, hi):
            intervals.append((s, e))
            ops[base_name(name)] += (e - s) * 1e-9
            op_calls[base_name(name)] += 1
        if MODULES_LINE in lines:
            for name, s, e in _clipped(lines[MODULES_LINE], lo, hi):
                modules[base_name(name)] += (e - s) * 1e-9
                module_runs[base_name(name)] += 1
        merged = _union(intervals)
        busy_ns += sum(e - s for s, e in merged)
        if i == 0:
            ends = [(lo, lo)] + merged + [(hi, hi)]
            gaps = [(a[1], b[0]) for a, b in zip(ends, ends[1:])
                    if b[0] > a[1]]
    gaps.sort(key=lambda g: g[0] - g[1])
    n_dev = len(devices)
    return {
        "devices": n_dev,
        "marked": bool(marks),
        "busy_s": busy_ns * 1e-9 / max(n_dev, 1),
        "window_s": (hi - lo) * 1e-9,
        "ops": dict(ops),
        "op_calls": dict(op_calls),
        "modules": dict(modules),
        "module_runs": dict(module_runs),
        "idle_gaps": [(_label(s, e, host), (e - s) * 1e-9)
                      for s, e in gaps[:10]],
    }


def _label(s: int, e: int, host) -> str:
    """Name of the host event that overlaps [s, e) the most (the innermost,
    shortest one on ties), or "no host event"."""
    best, best_key = "no host event", (0, 0)
    for name, hs, he in host:
        ov = min(e, he) - max(s, hs)
        if ov > 0:
            key = (ov, -(he - hs))
            if key > best_key:
                best, best_key = name, key
    return best


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


def total(table: Dict[str, float], needle: str) -> float:
    """Sum of the entries whose name contains ``needle``."""
    return sum(v for k, v in table.items() if needle in k)


def top(table: Dict[str, float], n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
