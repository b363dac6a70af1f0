"""compact_roofline.extract: the block compaction's share of its roofline.

Device time is the summed duration of the ``_where_compact`` programs in
the trace: each turns a where launch's lanes of matches into row indices
by a binary search of the running match count. The work of each is that
search for every row the launch gathers (``stats["launched_rows"]`` over
``stats["launches"]``): per row ceil(log2 n) probes of a 4-byte count,
two operations a probe, and the 4-byte row written. Nothing when the
program compacts no blocks (no ``_where_compact`` program ran)."""
import math

from chipbench import peaks

PATTERN = r"_where_compact"
COUNT_BYTES = 4


def read(obs):
    d = obs.stats_delta
    if obs.trace is None or not d.get("launches"):
        return None
    seconds, n = obs.trace.stage(PATTERN)
    if not n or seconds <= 0:
        return None
    rows = n * d["launched_rows"] / d["launches"]
    probes = math.ceil(math.log2(obs.work["rows"]))
    nbytes = rows * (probes + 1) * COUNT_BYTES
    return peaks.roofline_share(2.0 * rows * probes, nbytes, seconds,
                                peaks.peaks_for(obs.device_kind))
