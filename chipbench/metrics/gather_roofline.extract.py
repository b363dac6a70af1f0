"""gather_roofline.extract: the split row gather's share of its roofline
in the filtered pull.

Device time is the summed duration of the ``_packed_split_rows`` programs
in the trace; in this cell each is one where launch's gather. The work of
each is the rows it served (padding rows are not work), taken as the
window's served rows per launch (``stats["rows"]`` over
``stats["launches"]``): an index, one packed word per column, each
column's table row and the output row (:func:`chipbench.work.gather`)."""
from chipbench import peaks, work

PATTERN = r"_packed_split_rows"


def read(obs):
    d = obs.stats_delta
    if obs.trace is None or not d.get("launches"):
        return None
    seconds, n = obs.trace.stage(PATTERN)
    if not n or seconds <= 0:
        return None
    w = obs.work
    ops, nbytes = work.gather(n * d["rows"] / d["launches"], w["columns"],
                              w["table_bytes_per_row"],
                              w["out_bytes_per_row"])
    return peaks.roofline_share(ops, nbytes, seconds,
                                peaks.peaks_for(obs.device_kind))
