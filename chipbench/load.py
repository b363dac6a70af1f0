"""What every traffic mix shares: seeded streams, the Zipf law, the
profiler's window, the record of a window, and the finder of parts.

A mix file (``chipbench/traffic/<mix>.json``) is data: it names a
``loop``, the driver that offers it (``chipbench/loops/<loop>.py``), and
the parameters that driver reads. A driver names further parts in the
same way: an arrival process (``chipbench/arrivals/<dist>.py``), a law of
request sizes (``chipbench/sizes/<dist>.py``), a law of keys
(``chipbench/keys/<dist>.py``), or a query op (``chipbench/ops/<op>.py``).
Each part is found by its name under the checkout, so a new kind of
traffic is new files, and a new cell of a known kind is a data file alone.

A driver's module holds a class ``Loop(mix, dep, seed, seconds)`` with
``warm()``, ``run(tracer) -> Observed``, ``compare(ref, raw, answers)``
and ``control(ref, raw)``.
"""
from __future__ import annotations

import importlib.util
import math
import pathlib
import sys
import threading
import time
from dataclasses import dataclass, field

import numpy as np

TRACE_HOST_LEVEL = 2
TRACE_SECONDS = 10.0     # the profiler covers at most this much of a window
HEARTBEAT_S = 0.005      # a traced window's heartbeat sleeps this long


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def part(root, kind: str, name: str):
    """The module ``chipbench/<kind>/<name>.py`` of the checkout ``root``."""
    return load_module(pathlib.Path(root) / "chipbench" / kind / f"{name}.py",
                       f"chipbench_{kind}_{name}")


def loop_for(mix: dict, dep, seed: int, seconds: float, **kw):
    """The driver the mix names, set up for one window."""
    return part(dep.root, "loops", mix["loop"]).Loop(mix, dep, seed, seconds,
                                                     **kw)


def rng_for(seed: int, *keys: int) -> np.random.Generator:
    """An independent stream for each (seed, keys) — a column, a table,
    the traffic — so threads may draw them in any order."""
    return np.random.default_rng([seed % (1 << 63), *keys])


def affine(rng: np.random.Generator, k: int) -> tuple[int, int]:
    """(a, b) with gcd(a, k) = 1: x -> (a x + b) mod k is a bijection."""
    while True:
        a = int(rng.integers(1, max(k, 2)))
        if math.gcd(a, k) == 1:
            return a, int(rng.integers(0, k))


def zipf_ranks(rng: np.random.Generator, n: int, k: int,
               s: float) -> np.ndarray:
    """``n`` ranks in [0, k) with density ~ (rank + 1)^-s: the inverse of
    the continuous power law's CDF, floored."""
    u = rng.random(n)
    e = 1.0 - s
    x = (1.0 + u * ((k + 1.0) ** e - 1.0)) ** (1.0 / e) \
        if abs(e) > 1e-9 else np.exp(u * math.log(k + 1.0))
    return np.clip(x.astype(np.int64) - 1, 0, k - 1)


@dataclass
class Observed:
    """What one measured window saw, for the metric readers."""
    loop: str
    seconds: float
    attempted: int = 0
    failed: int = 0
    latency_ms: np.ndarray | None = None     # every request, failures high
    gen_lag_ms: np.ndarray | None = None     # send minus due, untraced part
    completed_in_window: float = 0    # requests done; queries, in-flight
                                      # ones by the share run in the window
    service_class: dict | None = None        # class_stats(), untraced part
    stats_delta: dict = field(default_factory=dict)
    trace: object = None                     # chipbench.trace.DeviceTrace
    rows_traced: int = 0                     # requested rows sent traced
    work: dict = field(default_factory=dict)
    device_kind: str = ""
    answers: list = field(default_factory=list)   # (key, answer) checked
    backlog: tuple = ()          # requests sent, not done: mid-window, close
    host_stall_max_ms: float | None = None   # traced runs, untraced part


def delta(before: dict, after: dict) -> dict:
    """The service's numeric counters moved over a window."""
    return {k: v - before[k] for k, v in after.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


class Tracer:
    """The profiler over the last ``TRACE_SECONDS`` of a measured window
    (no-op when ``trace_dir`` is None): host events at level 2, no Python
    tracer. A timer thread starts it, so the load runs on unchanged, and
    the trace is written after the window, where it disturbs no request.

    The part of the window before ``t_host`` is untraced: host-clock
    readings of a traced run are taken there (the whole window when
    nothing is traced). ``on_trace`` runs at ``t_host``, in the timer's
    thread, just before the profiler starts; ``t0``/``t1`` bound the trace.

    A traced window also runs a heartbeat: a thread that sleeps
    ``HEARTBEAT_S`` at a time. ``stall_max_s`` is the longest it went
    without waking before ``t_host``: how long the host held every thread
    of the process still."""

    def __init__(self, trace_dir: str | None, on_trace=None):
        self.dir = trace_dir
        self.on_trace = on_trace
        self.t_host = math.inf
        self.t0 = self.t1 = 0.0
        self.stall_max_s = 0.0
        self._timer: threading.Timer | None = None
        self._heart: threading.Thread | None = None
        self._closed = threading.Event()

    def _beat(self) -> None:
        last = time.perf_counter()
        while not self._closed.is_set():
            time.sleep(HEARTBEAT_S)
            now = time.perf_counter()
            if now <= self.t_host:
                self.stall_max_s = max(self.stall_max_s, now - last)
            last = now

    def _begin(self) -> None:
        import jax
        self.t_host = time.perf_counter()
        if self.on_trace is not None:
            self.on_trace()
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = TRACE_HOST_LEVEL
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()

    def start(self, seconds: float) -> None:
        if self.dir is None:
            return
        self._timer = threading.Timer(max(0.0, seconds - TRACE_SECONDS),
                                      self._begin)
        self._timer.start()
        self._heart = threading.Thread(target=self._beat,
                                       name="chipbench-heartbeat")
        self._heart.start()

    def join(self) -> None:
        """At the window's close: stop the profiler and write the trace."""
        if self.dir is None:
            return
        self._timer.join()
        self._closed.set()
        self._heart.join()
        import jax
        self.t1 = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self):
        if self.dir is None:
            return None
        from chipbench import trace
        return trace.reduce_file(trace.find_xplane(self.dir),
                                 self.t1 - self.t0)
