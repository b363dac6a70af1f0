"""where_ms.extract: the mean duration of the traced ``serve.where``
spans, in milliseconds: the submit-side predicate scan, the running match
count and the one scalar sync of a where request, before its lanes are
queued. It includes the wait for the device work queued ahead of the
scan. Nothing when the program writes no such span."""
import pathlib

from chipbench.trace import find_xplane

CELL = "lineitem-extract"
SPAN = "serve.where"


def read(obs):
    if obs.trace is None or obs.trace.window_s <= 0:
        return None
    root = pathlib.Path(__file__).resolve().parents[2]
    try:
        path = find_xplane(str(root / ".chipbench" / "trace" / CELL))
    except FileNotFoundError:
        return None
    from jax.profiler import ProfileData
    ns = [ev.duration_ns for plane in ProfileData.from_file(path).planes
          if plane.name.startswith("/host:")
          for line in plane.lines for ev in line.events if ev.name == SPAN]
    return 1e-6 * sum(ns) / len(ns) if ns else None
