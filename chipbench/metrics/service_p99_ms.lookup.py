"""service_p99_ms.lookup: the service's own submit-to-retire p99 for the
request class (``FeatureService.class_stats()``), over the requests
retired once the profiler's trace was written: ``reset_latency_window()``
runs at the window's start and again when the trace is written. It leaves
out the client's wait for the result and the generator's lag."""


def read(obs):
    c = obs.service_class
    if not c or not c.get("samples"):
        return None
    return float(c["p99_ms"])
