"""Open loop: requests arrive on a fixed schedule whatever the system does
(independent online users), and each is timed from the moment it was due
to the moment its result was in hand.

Mix keys: ``rate_per_s`` (times the window gives the request count),
``arrivals`` (an arrival process from ``chipbench/arrivals``),
``rows_per_request`` (a law from ``chipbench/sizes``), ``row_ids`` (a
law from ``chipbench/keys``), ``klass`` (the front door's request class),
``check_sample`` requests compared, ``check_longest`` of them the longest.
Every seed offers the same request sizes and gaps in another order. A
request is row ids through the front door (``FeatureFrontend.submit`` /
``result``).
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass

import numpy as np

from chipbench.load import Observed, Tracer, delta, part, rng_for


@dataclass
class Schedule:
    """An open loop's requests: due times (s after the window opens),
    row ids, and the requests whose answers are checked."""
    due: np.ndarray
    offsets: np.ndarray          # request i is rows[offsets[i]:offsets[i+1]]
    rows: np.ndarray
    check: np.ndarray

    def request(self, i: int) -> np.ndarray:
        return self.rows[self.offsets[i]:self.offsets[i + 1]]

    @property
    def sizes(self) -> np.ndarray:
        return np.diff(self.offsets)


def schedule(mix: dict, root, seed: int, seconds: float, n_rows: int,
             rate: float | None = None) -> Schedule:
    rate = mix["rate_per_s"] if rate is None else rate
    n = max(1, round(rate * seconds))
    rng = rng_for(seed, 10)
    q = (np.arange(n) + 0.5) / n
    arrivals = mix["arrivals"]
    due = part(root, "arrivals", arrivals["dist"]).due(arrivals, q, rng,
                                                        seconds)
    spec = mix["rows_per_request"]
    sizes = rng.permutation(part(root, "sizes", spec["dist"]).quantile(spec,
                                                                       q))
    ids = mix["row_ids"]
    rows = part(root, "keys", ids["dist"]).draw(ids, rng, int(sizes.sum()),
                                                n_rows)
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    k = min(n, mix["check_sample"])
    longest = np.argsort(-sizes, kind="stable")[:min(k, mix["check_longest"])]
    rest = np.setdiff1d(np.arange(n), longest)
    check = np.union1d(longest, rng.choice(rest, min(k - longest.size,
                                                     rest.size),
                                           replace=False))
    return Schedule(due=due, offsets=offsets, rows=rows, check=check)


class Loop:
    """Open-loop rows traffic through the front door."""

    def __init__(self, mix: dict, dep, seed: int, seconds: float,
                 rate: float | None = None):
        self.mix, self.dep, self.seconds = mix, dep, seconds
        self.sched = schedule(mix, dep.root, seed, seconds, dep.raw.rows,
                              rate)
        self.klass = mix["klass"]

    def warm(self) -> None:
        """Serve one request of each launch size the schedule uses, twice."""
        buckets = np.asarray(self.dep.service.buckets)
        used = np.unique(buckets[np.minimum(
            np.searchsorted(buckets, self.sched.sizes), buckets.size - 1)])
        front = self.dep.frontend
        for _ in range(2):
            for b in used:
                rows = np.resize(self.sched.rows, int(b))
                front.result(front.submit(rows, klass=self.klass),
                             timeout=600)

    def run(self, tracer: Tracer, grace_s: float = 60.0) -> Observed:
        from repro.serve import Overloaded, ServeError
        sched, front, svc = self.sched, self.dep.frontend, self.dep.service
        n = sched.due.size
        sent = np.full(n, np.nan)
        done = np.full(n, np.nan)
        ok = np.zeros(n, bool)
        kept: dict[int, np.ndarray] = {}
        check = set(sched.check.tolist())
        tickets: queue.SimpleQueue = queue.SimpleQueue()
        svc.reset_latency_window()
        untraced: dict = {}        # the class's record when tracing starts
        tracer.on_trace = lambda: untraced.update(
            svc.class_stats()[self.klass])
        before = dict(svc.throughput_stats(1.0))
        tracer.start(self.seconds)
        t0 = time.perf_counter() + 0.01
        due = t0 + sched.due
        close = t0 + self.seconds

        def send() -> None:
            for i in range(n):
                wait = due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                sent[i] = time.perf_counter()
                try:
                    tickets.put((i, front.submit(sched.request(i),
                                                 klass=self.klass)))
                except Overloaded:
                    done[i] = sent[i]
            tickets.put(None)

        def collect() -> None:
            while (item := tickets.get()) is not None:
                i, t = item
                try:
                    out = front.result(t, timeout=max(
                        close + grace_s - time.perf_counter(), 0.001))
                except (ServeError, TimeoutError):
                    done[i] = time.perf_counter()
                    continue
                done[i] = time.perf_counter()
                ok[i] = True
                if i in check:
                    kept[i] = out

        threads = [threading.Thread(target=send, name="chipbench-send"),
                   threading.Thread(target=collect, name="chipbench-collect")]
        for th in threads:
            th.start()
        threads[0].join()
        threads[1].join()
        tracer.join()
        end = time.perf_counter()
        after = svc.throughput_stats(1.0)
        lat = np.where(ok, done - due, np.nan_to_num(end - due))
        in_trace = (sent >= tracer.t0) & (sent <= tracer.t1) if tracer.dir \
            else np.zeros(n, bool)
        obs = Observed(
            loop="open", seconds=self.seconds, attempted=n,
            failed=int(n - ok.sum()), latency_ms=lat * 1e3,
            gen_lag_ms=(sent - due)[sent < tracer.t_host] * 1e3,
            completed_in_window=int((ok & (done <= close)).sum()),
            service_class=(untraced if tracer.dir
                           else svc.class_stats()[self.klass]),
            stats_delta=delta(before, after),
            rows_traced=int(sched.sizes[in_trace].sum()))
        obs.answers = [(i, kept[i]) for i in sorted(kept)]
        obs.backlog = tuple(
            int((sent <= t).sum() - (done <= t).sum())
            for t in (t0 + self.seconds / 2, close))
        return obs

    def compare(self, ref, raw, answers) -> dict:
        """The configuration's numbers over the checked requests."""
        if not answers:
            return {}
        rows = np.concatenate([self.sched.request(i) for i, _ in answers])
        served = np.concatenate([a for _, a in answers])
        return ref.compare_rows(raw, rows, served)

    def control(self, ref, raw) -> dict:
        """The same numbers with the lower-precision reference served, at
        the requests a run checks."""
        rows = np.concatenate([self.sched.request(i)
                               for i in self.sched.check])
        return ref.compare_rows(raw, rows, None, control=True)
