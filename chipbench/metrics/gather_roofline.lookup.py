"""gather_roofline.lookup: the split row gather's share of its roofline.

Device time is the summed duration of the ``_packed_split_rows`` programs
in the trace. The work is that of every row requested by a request sent
while the profiler ran (padding rows are not work): an index, one packed
word per column, each column's table row and the output row
(:func:`chipbench.work.gather`)."""
from chipbench import peaks, work

PATTERN = r"_packed_split_rows"


def read(obs):
    if obs.trace is None or obs.rows_traced <= 0:
        return None
    seconds, n = obs.trace.stage(PATTERN)
    if not n or seconds <= 0:
        return None
    w = obs.work
    ops, nbytes = work.gather(obs.rows_traced, w["columns"],
                              w["table_bytes_per_row"],
                              w["out_bytes_per_row"])
    return peaks.roofline_share(ops, nbytes, seconds,
                                peaks.peaks_for(obs.device_kind))
