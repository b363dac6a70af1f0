"""gen_lag_p99_ms.lookup: how late the open-loop generator sent, the 99th
percentile of send time minus due time on the generator's own clock, over
the requests sent once the profiler's trace was written (the whole window
when nothing is traced). A high value means the load was not offered as
scheduled."""
import numpy as np


def read(obs):
    if obs.gen_lag_ms is None or obs.gen_lag_ms.size == 0:
        return None
    return float(np.percentile(obs.gen_lag_ms, 99))
