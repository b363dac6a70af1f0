"""Reduce a JAX profiler trace to device busy time and per-stage time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's own ``ProfileData``. Each TPU chip is one plane named
``/device:TPU:<n>``; on it the ``XLA Ops`` line holds one event per device
operation and the ``XLA Modules`` line one event per executed program,
named after the jitted function (``jit_<name>(<id>)``). Busy time is the
union of a chip's op intervals; a stage's time is the summed duration of
the modules whose names match the stage's pattern.

    python3 -m chipbench.trace <file.xplane.pb>   # what a trace holds
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


@dataclass
class DeviceTrace:
    window_s: float                  # traced window, host clock
    busy_s: float                    # union of op intervals, mean over chips
    chips: int
    modules: list[tuple[str, float]] = field(default_factory=list)
    top_ops: list[tuple[str, float]] = field(default_factory=list)
    idle_gaps: list[tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - min(self.busy_s, self.window_s) / self.window_s

    def stage(self, pattern: str) -> tuple[float, int]:
        """(summed seconds, executions) of the modules matching
        ``pattern`` (a regular expression searched in the module name)."""
        rx = re.compile(pattern)
        hits = [s for name, s in self.modules if rx.search(name)]
        return float(sum(hits)), len(hits)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _host_label(host: list[tuple[float, float, str]], t: float) -> str:
    """The innermost host event that covers time ``t`` (latest start)."""
    best = None
    for s, e, name in host:
        if s <= t <= e and (best is None or s > best[0]):
            best = (s, name)
    return best[1] if best else "host idle (no event)"


def reduce_profile(profile, window_s: float) -> DeviceTrace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`DeviceTrace`."""
    busy, chips = [], 0
    modules: list[tuple[str, float]] = []
    ops: collections.Counter = collections.Counter()
    host: list[tuple[float, float, str]] = []
    first_chip_union: list[tuple[float, float]] = []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            intervals = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for ev in line.events:
                        intervals.append((ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
                        ops[ev.name] += ev.duration_ns * 1e-9
                elif line.name == MODULES_LINE:
                    modules.extend((ev.name, ev.duration_ns * 1e-9)
                                   for ev in line.events)
            if intervals:
                chips += 1
                merged = _union(intervals)
                busy.append(sum(e - s for s, e in merged) * 1e-9)
                if not first_chip_union:
                    first_chip_union = merged
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                             f"{line.name}: {ev.name}")
                            for ev in line.events)
    gaps = sorted(((b[0] - a[1], (a[1] + b[0]) / 2)
                   for a, b in zip(first_chip_union, first_chip_union[1:])),
                  reverse=True)[:TOP]
    return DeviceTrace(
        window_s=window_s,
        busy_s=sum(busy) / chips if chips else 0.0,
        chips=chips, modules=modules,
        top_ops=[(n, s) for n, s in ops.most_common(TOP)],
        idle_gaps=[(_host_label(host, mid), g * 1e-9) for g, mid in gaps])


def reduce_file(path: str, window_s: float) -> DeviceTrace:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), window_s)


def dump(path: str, per_line: int = 5) -> None:
    """Print each plane's lines with event counts and a few events."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    for plane in prof.planes:
        print(f"plane {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                stats = {k: v for k, v in ev.stats}
                print(f"    {ev.name!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns} stats={str(stats)[:300]}")


if __name__ == "__main__":
    dump(sys.argv[1])
