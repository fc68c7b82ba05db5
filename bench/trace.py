"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers, with nothing but the profiler's own reader.

* Device planes are ``/device:TPU:<n>``. On each, the ``XLA Ops`` line
  holds one event per operation run and the ``XLA Modules`` line one
  event per program run.
* The traced window is the host event the harness opens and closes
  around the windows it traces (:data:`WINDOW_EVENT`).
* Busy time is the union of operation intervals on a device plane inside
  the window; the idle share is ``1 - busy / window``, averaged over the
  chips used.
* A program's device time is the summed duration of its module events
  inside the window, found by name patterns that each metric keeps.
* The breakdown lists the operations that took most time, and the
  longest idle gaps, each named by the host event that overlaps it most.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_EVENT = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"[\(\.]\d+\)?$")


@dataclass
class Device:
    name: str
    ops: list[tuple[str, float, float]] = field(default_factory=list)
    modules: list[tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: list[Device]
    host: list[tuple[str, float, float]]
    window: tuple[float, float] | None


def find(directory: str | Path) -> Path:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return files[-1]


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, host, window = [], [], None
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops += [(op_name(e.name), e.start_ns,
                                 e.start_ns + e.duration_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    dev.modules += [(e.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name == WINDOW_EVENT:
                        window = span[1:]
                    elif e.duration_ns > 0:
                        host.append(span)
    devices.sort(key=lambda d: int(_DEVICE.match(d.name).group(1)))
    return Trace(devices=devices, host=host, window=window)


def union(spans, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged ``(start, end)`` intervals of ``spans`` clipped to
    ``[lo, hi]``."""
    out: list[list[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for _, s, e in spans):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """Idle intervals of ``[lo, hi]`` between merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(text: str) -> str:
    """An operation's name from its HLO text
    (``%fusion.3 = f32[...] fusion(...)`` → ``fusion.3``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def base_name(name: str) -> str:
    """A program or op name without its numeric suffix
    (``jit_fold_body(12)`` → ``jit_fold_body``, ``fusion.3`` →
    ``fusion``)."""
    return _SUFFIX.sub("", name)


def program_seconds(dev: Device, patterns, lo: float, hi: float) -> float:
    """Device seconds of the programs whose names match any of
    ``patterns`` (regular expressions), inside ``[lo, hi]``."""
    rx = [re.compile(p) for p in patterns]
    return sum(min(e, hi) - max(s, lo) for n, s, e in dev.modules
               if e > lo and s < hi and any(r.search(n) for r in rx)) * 1e-9


def summarize(trace: Trace, *, n_devices: int, top: int = 10) -> dict | None:
    """Busy and window seconds, averaged over the first ``n_devices``
    device planes, and the breakdown. ``None`` where the trace holds no
    window or no device plane to read."""
    if trace.window is None or len(trace.devices) < n_devices:
        return None
    lo, hi = trace.window
    devs = trace.devices[:n_devices]
    per_busy, op_time, gap_list = [], {}, []
    for dev in devs:
        busy = union(dev.ops, lo, hi)
        per_busy.append(sum(e - s for s, e in busy) * 1e-9)
        mods = sorted((s, e, base_name(n)) for n, s, e in dev.modules)
        starts = [m[0] for m in mods]
        for name, s, e in dev.ops:
            if e <= lo or s >= hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and e <= mods[i][1] else ""
            key = f"{mod}/{base_name(name)}" if mod else base_name(name)
            op_time[key] = op_time.get(key, 0.0) + (min(e, hi) - max(s, lo))
        gap_list += gaps(busy, lo, hi)
    gap_list.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gap_list[:top]:
        best, overlap, best_len = "untraced host code", 0.0, float("inf")
        for n, hs, he in trace.host:
            ov = min(he, e) - max(hs, s)
            # the innermost event that covers most of the gap names it
            if ov > overlap or (ov == overlap and ov > 0
                                and he - hs < best_len):
                best, overlap, best_len = n, ov, he - hs
        named.append([best, (e - s) * 1e-9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": sum(per_busy) / len(per_busy),
            "window_s": (hi - lo) * 1e-9,
            "breakdown": {"device_ops": [[k, v * 1e-9] for k, v in ops],
                          "idle_gaps": named}}
