"""hist_roofline.q6: the split masked histogram's share of its roofline.

Device time is the summed duration of the ``_masked_counts_split``
programs in the trace; each execution is one histogram of
``l_extendedprice`` over every row under a mask
(:func:`chipbench.work.hist`)."""
from chipbench import peaks, work

PATTERN = r"_masked_counts_split"
COLUMN = "l_extendedprice"


def read(obs):
    if obs.trace is None:
        return None
    seconds, n = obs.trace.stage(PATTERN)
    if not n or seconds <= 0:
        return None
    ops, nbytes = work.hist(obs.work["rows"],
                            obs.work["device_bits"][COLUMN],
                            obs.work["cardinality"][COLUMN])
    return peaks.roofline_share(n * ops, n * nbytes, seconds,
                                peaks.peaks_for(obs.device_kind))
