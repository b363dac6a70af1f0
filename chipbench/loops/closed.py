"""Closed loop: ``clients`` threads each send a query and wait for its
answer before the next (callers that wait on each reply).

Mix keys: ``clients``, ``op`` (the query op, ``chipbench/ops/<op>.py``),
``query`` (its name in the configuration, whose reference draws the
parameters), ``check_sample`` answers compared, drawn from the seed.
"""
from __future__ import annotations

import threading
import time

from chipbench.load import Observed, Tracer, delta, part, rng_for


class Loop:
    """Closed-loop clients, each issuing one query at a time."""

    def __init__(self, mix: dict, dep, seed: int, seconds: float):
        self.mix, self.dep, self.seconds, self.seed = mix, dep, seconds, seed
        self.query = mix["query"]
        self.op = part(dep.root, "ops", mix["op"])

    def warm(self) -> None:
        rng = rng_for(self.seed, 30)
        for _ in range(2):
            self.op.ask(self.dep, self.query,
                        self.dep.ref.query_params(self.query, rng))

    def run(self, tracer: Tracer, grace_s: float = 60.0) -> Observed:
        svc = self.dep.service
        records: list[list] = [[] for _ in range(self.mix["clients"])]
        before = dict(svc.throughput_stats(1.0))
        tracer.start(self.seconds)
        t0 = time.perf_counter()
        close = t0 + self.seconds

        def client(c: int) -> None:
            rng = rng_for(self.seed, 20, c)
            while time.perf_counter() < close:
                params = self.dep.ref.query_params(self.query, rng)
                start = time.perf_counter()
                try:
                    got = self.op.ask(self.dep, self.query, params)
                except Exception as e:    # a failed query is counted, not fatal
                    records[c].append((params, None, start,
                                       time.perf_counter(), repr(e)))
                    continue
                records[c].append((params, got, start, time.perf_counter(),
                                   None))

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"chipbench-client-{c}")
                   for c in range(self.mix["clients"])]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=self.seconds + grace_s)
        tracer.join()
        after = svc.throughput_stats(1.0)
        flat = [r for rs in records for r in rs]
        good = [r for r in flat if r[4] is None]
        rng = rng_for(self.seed, 31)
        k = min(len(good), self.mix["check_sample"])
        pick = sorted(rng.choice(len(good), k, replace=False)) if k else []
        obs = Observed(
            loop="closed", seconds=self.seconds, attempted=len(flat),
            failed=len(flat) - len(good),
            completed_in_window=sum(
                max(0.0, min(end, close) - start) / (end - start)
                for _, _, start, end, _ in good if end > start),
            stats_delta=delta(before, after))
        obs.answers = [(good[i][0], good[i][1]) for i in pick]
        errors = sorted({r[4] for r in flat if r[4] is not None})
        for e in errors[:3]:
            print(f"query failed: {e}", flush=True)
        return obs

    def compare(self, ref, raw, answers) -> dict:
        if not answers:
            return {}
        return self.op.compare(ref, raw, answers)

    def control(self, ref, raw) -> dict:
        """The same number with the lower-precision reference served, at
        the first queries of each client's stream."""
        params = []
        for c in range(self.mix["clients"]):
            rng = rng_for(self.seed, 20, c)
            params += [ref.query_params(self.query, rng)
                       for _ in range(-(-self.mix["check_sample"]
                                        // self.mix["clients"]))]
        return self.op.control(ref, raw, params)
