"""queue_wait_p99_ms.lookup: the request class's 99th percentile of queue
wait, from a chunk's enqueue at submit to the pump's take
(``class_stats()["queue_p99_ms"]``), over the chunks taken once the
profiler's trace was written: ``reset_latency_window()`` runs at the
window's start and again when the trace is written. Nothing when the
program does not report it."""


def read(obs):
    c = obs.service_class
    if not c or not c.get("samples") or "queue_p99_ms" not in c:
        return None
    return float(c["queue_p99_ms"])
