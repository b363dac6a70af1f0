"""host_stall_max_ms.lookup: the longest time a heartbeat thread that
sleeps 5 ms at a time went without waking, over the traced window's part
after the trace was written. Every thread of the process stands still that
long; a stall that long stalls the load and the service alike."""


def read(obs):
    if obs.host_stall_max_ms is None or obs.host_stall_max_ms <= 0:
        return None
    return float(obs.host_stall_max_ms)
