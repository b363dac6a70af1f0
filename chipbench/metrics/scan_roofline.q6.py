"""scan_roofline.q6: the split predicate scan's share of its roofline.

Device time is the summed duration of the scan programs (``_scan_body``)
in the trace; each execution is one Q6 scan over every row of its three
predicate columns (:func:`chipbench.work.scan`)."""
from chipbench import peaks, work

PATTERN = r"_scan_body"
COLUMNS = ("l_shipdate", "l_discount", "l_quantity")


def read(obs):
    if obs.trace is None:
        return None
    seconds, n = obs.trace.stage(PATTERN)
    if not n or seconds <= 0:
        return None
    bits = [obs.work["device_bits"][c] for c in COLUMNS]
    ops, nbytes = work.scan(obs.work["rows"], bits)
    return peaks.roofline_share(n * ops, n * nbytes, seconds,
                                peaks.peaks_for(obs.device_kind))
