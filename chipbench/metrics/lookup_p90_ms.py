"""lookup_p90_ms: the 90th percentile of every request's latency, from
the moment the open-loop schedule made it due to the moment its result
was in hand. A refused or failed request counts with the time to the end
of the run, so it misses any limit."""
import numpy as np


def read(obs):
    if obs.latency_ms is None or obs.latency_ms.size == 0:
        return None
    return float(np.percentile(obs.latency_ms, 90))
