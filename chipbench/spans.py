"""Read the program's own spans out of a cell's profiler trace, and the
chip's idle time they cover.

The program writes host spans through ``jax.profiler.TraceAnnotation``
(``repro.spans``); the profiler writes them with the device planes, on one
clock. A traced run (``--trace 1``) leaves its ``.xplane.pb`` under
``<checkout>/.chipbench/trace/<cell>/``. From it this module takes

- each program span by name: start and end (ns), metadata, and the host
  line (thread) it ran on;
- the chip's idle intervals: the gaps between the merged op intervals of
  the first TPU plane, formed as :mod:`chipbench.trace` forms them;

and intersects the two. A program without spans gives no spans, and a
metric that reads them then reads nothing.

    python3 -m chipbench.spans <file.xplane.pb>   # spans and idle time
"""
from __future__ import annotations

import functools
import os
import pathlib
import sys
from dataclasses import dataclass, field

from chipbench.trace import DEVICE_PLANE, OPS_LINE, _union, find_xplane

NAMES = ("serve.submit", "pump.wait", "pump.launch", "pump.retire",
         "pump.fetch", "query.agg_where", "query.fetch")


@dataclass(frozen=True)
class Span:
    start: int                # ns, the trace's clock
    end: int
    args: dict
    line: tuple[int, int]     # (plane, line) index: one host thread


@dataclass
class ProgramTrace:
    spans: dict[str, list[Span]] = field(default_factory=dict)
    idle: list[tuple[int, int]] = field(default_factory=list)   # ns
    chips: int = 0

    def named(self, name: str) -> list[Span]:
        return self.spans.get(name, [])

    def idle_ns(self) -> int:
        return sum(e - s for s, e in self.idle)


def covered(intervals, spans) -> int:
    """Nanoseconds of the disjoint, sorted ``intervals`` that lie inside
    the union of ``spans`` (``Span``s or ``(start, end)`` pairs)."""
    merged = _union([(s.start, s.end) if isinstance(s, Span) else s
                     for s in spans])
    total, j = 0, 0
    for s, e in intervals:
        while j < len(merged) and merged[j][1] <= s:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < e:
            total += min(e, merged[k][1]) - max(s, merged[k][0])
            k += 1
    return total


def self_ns(parent: Span, children: list[Span]) -> int:
    """The parent's duration less the part its children on the same
    thread cover."""
    kids = [c for c in children if c.line == parent.line]
    return parent.end - parent.start - covered([(parent.start, parent.end)],
                                                kids)


def from_profile(profile) -> ProgramTrace:
    """The program's spans and the first chip's idle intervals of a
    ``jax.profiler.ProfileData``."""
    out = ProgramTrace(spans={n: [] for n in NAMES})
    for p, plane in enumerate(profile.planes):
        if DEVICE_PLANE.match(plane.name):
            ops = [(ev.start_ns, ev.start_ns + ev.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                out.chips += 1
                if out.chips == 1:
                    busy = _union(ops)
                    out.idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name in out.spans:
                        out.spans[ev.name].append(Span(
                            int(ev.start_ns),
                            int(ev.start_ns + ev.duration_ns),
                            {k: v for k, v in ev.stats}, (p, i)))
    for spans in out.spans.values():
        spans.sort(key=lambda s: s.start)
    return out


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int) -> ProgramTrace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def for_cell(metric_file: str, cell: str) -> ProgramTrace | None:
    """The spans of the cell's last traced run in the checkout that holds
    ``metric_file`` (``<checkout>/chipbench/metrics/<metric>.py``), or
    None when there is no trace."""
    root = pathlib.Path(metric_file).resolve().parents[2]
    try:
        path = find_xplane(str(root / ".chipbench" / "trace" / cell))
    except FileNotFoundError:
        return None
    return _read(path, os.stat(path).st_mtime_ns)


def for_run(obs, metric_file: str, cell: str,
            marker: str) -> ProgramTrace | None:
    """The spans of ``obs``'s run when it was traced and the program
    wrote ``marker`` spans in it; None otherwise (an untraced run, or a
    program without the span)."""
    if obs.trace is None or obs.trace.window_s <= 0:
        return None
    t = for_cell(metric_file, cell)
    return t if t is not None and t.named(marker) else None


def dump(path: str) -> None:
    from jax.profiler import ProfileData
    t = from_profile(ProfileData.from_file(path))
    print(f"chips {t.chips}, idle {t.idle_ns() * 1e-9:.6f} s "
          f"in {len(t.idle)} gaps")
    for name, spans in t.spans.items():
        total = sum(s.end - s.start for s in spans) * 1e-9
        print(f"{name}: {len(spans)} spans, {total:.6f} s, "
              f"{covered(t.idle, spans) * 1e-9:.6f} s of chip idle")


if __name__ == "__main__":
    dump(sys.argv[1])
