"""Chaos suite: launch-level fault isolation, failover, deadlines.

The invariant under test: a FeatureService under injected launch faults
either completes every ticket BIT-exact vs the fault-free reference (when
a healthy replica exists to fail over to) or resolves exactly the faulted
tickets to typed ServeErrors while everything else keeps serving — the
service itself never dies from a launch-path exception. Faults are
injected by :class:`repro.serve.faults.FaultInjector` ON the pump's
launch path, so they exercise the same recovery machinery a real device
error would.

Deterministic by construction: scripted rules fire on exact launch
sequences (no timing races), and breaker thresholds are raised wherever a
test's fault script must fully play out. The randomized sweep reads
``CHAOS_SWEEP_SEEDS`` (nightly sets it high; default keeps tier-1 quick).
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.columnar import Table
from repro.core import (FeatureSet, FeaturePlan, FeatureExecutor)
from repro.serve import (DeadlineExceeded, FaultInjector, FaultPolicy,
                         FeatureService, InjectedFault, ServeError,
                         default_classes)
from repro.serve.faults import StreamBreaker


def _mixed_table(n=3000, imcu_rows=700, seed=0):
    rng = np.random.default_rng(seed)
    t = Table.from_data({
        "age": rng.integers(18, 80, n),
        "state": np.array(["CA", "OR", "WA", "NY"])[rng.integers(0, 4, n)],
        "income": rng.integers(20, 200, n) * 1000,
    }, imcu_rows=imcu_rows)
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("income", "minmax"))
    return t, fs


def _reference(t, fs, requests):
    """Fault-free ground truth: the unsharded int32 executor."""
    ex = FeatureExecutor(FeaturePlan(t, fs))
    return [np.asarray(ex.batch(r)) for r in requests]


# -- faults.py unit behavior ---------------------------------------------------------
def test_injector_rules_are_deterministic():
    inj = (FaultInjector()
           .fail_launches(2, shard=1)
           .delay_launches(0.0, 1, shard=0, after=1)
           .fail_launches(1, shard=0, stream=2, every=2))
    # shard-1 rule: exactly the next two shard-1 launches fail, then heal
    with pytest.raises(InjectedFault):
        inj.before_launch(1, 0)
    with pytest.raises(InjectedFault):
        inj.before_launch(1, 0)
    inj.before_launch(1, 0)                        # healed
    # shard-0 delay skips `after` matches, then fires once
    inj.before_launch(0, 0)
    inj.before_launch(0, 0)
    assert inj.delays_injected == 1
    # every=2 on (0, stream=2): first match skipped, second fires
    inj.before_launch(0, 2)
    with pytest.raises(InjectedFault):
        inj.before_launch(0, 2)
    assert inj.faults_injected == 3
    assert inj.launches_seen == 7


def test_injector_random_mode_seeded():
    a = FaultInjector(seed=7).random_faults(p_fail=0.5, max_events=10)
    b = FaultInjector(seed=7).random_faults(p_fail=0.5, max_events=10)
    pat_a = []
    for _ in range(40):
        try:
            a.before_launch(0, 0)
            pat_a.append(0)
        except InjectedFault:
            pat_a.append(1)
    pat_b = []
    for _ in range(40):
        try:
            b.before_launch(0, 0)
            pat_b.append(0)
        except InjectedFault:
            pat_b.append(1)
    assert pat_a == pat_b and sum(pat_a) == 10     # capped by max_events


def test_policy_backoff_and_breaker():
    p = FaultPolicy(backoff_s=0.01, backoff_cap_s=0.04)
    assert p.backoff_for(1) == 0.01
    assert p.backoff_for(2) == 0.02
    assert p.backoff_for(5) == 0.04                # capped
    with pytest.raises(ValueError):
        FaultPolicy(max_retries=-1)
    b = StreamBreaker()
    assert not b.strike(3, 1.0, now=0.0)
    assert not b.strike(3, 1.0, now=0.0)
    assert b.strike(3, 1.0, now=0.0)               # trips on the 3rd
    assert b.opened == 1
    assert b.is_open(3, now=0.5)
    assert not b.is_open(3, now=1.5)               # cooldown over: half-open
    assert not b.strike(3, 1.0, now=2.0)           # probe failed: re-open...
    assert b.is_open(3, now=2.5)                   # ...without re-counting
    b.reset()
    assert not b.is_open(3, now=2.5) and b.fails == 0


# -- acceptance: failover keeps availability at 1.0 ----------------------------------
def test_chaos_failover_bit_exact_availability_one():
    """>= 20 injected launch faults + 2 straggler episodes on a shard with
    2 replicas: every ticket completes bit-exact vs the fault-free
    reference, availability 1.0, failovers observed."""
    t, fs = _mixed_table()
    rng = np.random.default_rng(41)
    requests = [rng.integers(0, 700, rng.integers(8, 64))
                for _ in range(40)]                # all rows in shard 0
    requests += [np.arange(700 * s, 700 * s + 48) for s in (1, 2, 3)]
    want = _reference(t, fs, requests)
    inj = (FaultInjector()
           .fail_launches(12, shard=0, stream=0)
           .fail_launches(8, shard=0, stream=1)
           .delay_launches(0.12, 1, shard=0, stream=2, after=6)
           .delay_launches(0.12, 1, shard=1))
    # breaker effectively disabled so both fail rules play out in full and
    # the test stays deterministic whatever the launch interleaving
    pol = FaultPolicy(max_retries=3, backoff_s=0.001, breaker_fails=100,
                      straggler_min_s=0.05, straggler_warmup=3)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        svc.add_replica(0)
        svc.add_replica(0)
        tickets = [svc.submit(r) for r in requests]
        got = [svc.result(tk, timeout=60) for tk in tickets]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    st = svc.throughput_stats(1.0)
    assert inj.faults_injected >= 20
    assert inj.delays_injected == 2
    assert st["completed"] == st["requests"] == len(requests)
    assert st["availability"] == 1.0
    assert st["failed_tickets"] == 0
    assert st["failovers"] > 0
    assert st["retries"] >= 20


def test_chaos_no_replicas_isolates_faulted_shard():
    """Without replicas, a persistently failing shard takes down ONLY its
    own tickets — each resolves to a typed ServeError — while every other
    shard's tickets complete bit-exact, and the service accepts (and
    serves) new submits after the fault heals."""
    t, fs = _mixed_table()
    reqs_ok = [np.arange(700 * s + 8, 700 * s + 40) for s in (0, 1, 3)]
    reqs_bad = [np.arange(1400 + 16 * i, 1400 + 16 * i + 16)
                for i in range(5)]                 # shard 2 rows
    want_ok = _reference(t, fs, reqs_ok)
    # enough scripted faults that every shard-2 launch fails through all
    # retries: 5 tickets x (1 + 2 retries) = 15
    inj = FaultInjector().fail_launches(15, shard=2)
    pol = FaultPolicy(max_retries=2, backoff_s=0.001, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        tickets_ok = [svc.submit(r) for r in reqs_ok]
        tickets_bad = [svc.submit(r) for r in reqs_bad]
        for g, w in zip((svc.result(tk, timeout=60)
                         for tk in tickets_ok), want_ok):
            np.testing.assert_array_equal(g, w)
        for tk in tickets_bad:
            assert svc.poll(tk)                     # resolved, not hung
            with pytest.raises(ServeError) as ei:
                svc.result(tk, timeout=60)
            assert ei.value.shard == 2
            assert ei.value.attempts == 3           # 1 + max_retries
            assert isinstance(ei.value.__cause__, InjectedFault)
        st = dict(svc.stats)
        assert st["failed_tickets"] == len(reqs_bad)
        # the rules are exhausted (healed): the shard serves again
        again = np.arange(1400, 1464)
        np.testing.assert_array_equal(
            svc.result(svc.submit(again), timeout=60),
            _reference(t, fs, [again])[0])


def test_chaos_collect_mixes_results_and_errors():
    t, fs = _mixed_table()
    inj = FaultInjector().fail_launches(3, shard=1)
    pol = FaultPolicy(max_retries=2, backoff_s=0.001, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        t_ok = svc.submit(np.arange(0, 32))
        t_bad = svc.submit(np.arange(700, 732))
        out = svc.collect(timeout=60)
    assert isinstance(out[t_ok], np.ndarray)
    assert isinstance(out[t_bad], ServeError)
    np.testing.assert_array_equal(out[t_ok],
                                  _reference(t, fs, [np.arange(0, 32)])[0])


# -- breaker / monitor integration ---------------------------------------------------
def test_breaker_opens_and_monitor_rereplicates():
    """Consecutive failures open the primary's breaker (shard turns
    unhealthy); rebalance() grows a failover replica on a healthy device;
    retries drain through it and the breaker probe eventually closes."""
    t, fs = _mixed_table()
    inj = FaultInjector().fail_launches(3, shard=0, stream=0)
    pol = FaultPolicy(max_retries=5, backoff_s=0.001, breaker_fails=3,
                      breaker_cooldown_s=30.0)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol, max_replicas=2) as svc:
        tk = svc.submit(np.arange(0, 32))
        deadline = time.perf_counter() + 30
        while not svc.unhealthy and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert svc.unhealthy == [0]
        assert svc.stats["unhealthy_shards"] == 1
        acts = svc.rebalance()
        assert [s for s, _ in acts["failover_replicated"]] == [0]
        assert svc.replicas[0] == 1
        # the failover replica serves the stuck ticket bit-exact
        np.testing.assert_array_equal(
            svc.result(tk, timeout=60),
            _reference(t, fs, [np.arange(0, 32)])[0])
        assert svc.stats["failovers"] > 0
        # a second rebalance does NOT stack FAILOVER replicas (one healthy
        # copy already covers the shard) and never sheds the existing one
        # (policy 2 may still replicate shard 0 for plain load — all the
        # traffic is on it)
        acts2 = svc.rebalance()
        assert acts2["failover_replicated"] == []
        assert acts2["dropped"] == []
        assert svc.replicas[0] >= 1


def test_breaker_probe_recovers_stream():
    """After the cooldown the opened stream is half-open: the next launch
    probes it, a success closes the breaker (shard healthy again)."""
    t, fs = _mixed_table()
    inj = FaultInjector().fail_launches(2, shard=0, stream=0)
    pol = FaultPolicy(max_retries=5, backoff_s=0.001, breaker_fails=2,
                      breaker_cooldown_s=0.05)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)
        assert svc.stats["unhealthy_shards"] == 1
        time.sleep(0.06)                           # ride out the cooldown
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)  # the probe
        assert svc.unhealthy == []


# -- deadlines & timeouts ------------------------------------------------------------
def test_deadline_expires_queued_ticket():
    t, fs = _mixed_table()
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1) as svc:
        with pytest.raises(ValueError):
            svc.submit(np.arange(8), deadline_ms=0)
        svc.pause()                                # hold the queue
        tk = svc.submit(np.arange(0, 32), deadline_ms=20)
        time.sleep(0.05)                           # let it expire queued
        svc.resume()
        with pytest.raises(DeadlineExceeded) as ei:
            svc.result(tk, timeout=60)
        assert isinstance(ei.value, TimeoutError)  # generic catch works
        assert ei.value.ticket == tk
        assert svc.stats["timeouts"] == 1
        assert svc.stats["failed_tickets"] == 1
        # the expired ticket is gone from the ledger, service healthy
        svc.result(svc.submit(np.arange(0, 32), deadline_ms=60_000),
                   timeout=60)
        assert svc.stats["completed"] == 1


def test_result_and_drain_timeout_on_stuck_ticket():
    """A straggling launch makes result(timeout=) and drain(timeout=)
    raise builtin TimeoutError promptly — and the ticket still completes
    afterwards (a wait timeout never cancels work)."""
    t, fs = _mixed_table()
    inj = FaultInjector().delay_launches(0.6, 1, shard=0)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj) as svc:
        tk = svc.submit(np.arange(0, 32))
        t0 = time.perf_counter()
        with pytest.raises(TimeoutError):
            svc.result(tk, timeout=0.05)
        with pytest.raises(TimeoutError):
            svc.drain(timeout=0.05)
        assert time.perf_counter() - t0 < 0.5      # both bailed early
        np.testing.assert_array_equal(
            svc.result(tk, timeout=60),
            _reference(t, fs, [np.arange(0, 32)])[0])


# -- defensive paths: dead pump surfaced everywhere ----------------------------------
def _dying_service(monkeypatch):
    t, fs = _mixed_table(n=1400)
    svc = FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                         buckets=(64,), coalesce=1)
    boom = RuntimeError("pump infrastructure fault")

    def die():
        raise boom
    monkeypatch.setattr(svc, "_pick_action", die)
    return svc, boom


def test_pump_death_surfaces_from_every_entry_point(monkeypatch):
    """A pump-infrastructure error is terminal BY DESIGN — and every
    public entry point must report it promptly with the original error
    chained, rather than hanging or pretending to serve."""
    svc, boom = _dying_service(monkeypatch)
    with svc._lock:
        svc._work.notify_all()                     # wake into the fault
    svc._pump.join(timeout=10)
    assert not svc._pump.is_alive()
    for call in (lambda: svc.poll(0),
                 lambda: svc.submit(np.arange(8)),
                 lambda: svc.result(0),
                 svc.drain,
                 svc.collect,
                 svc.pause,
                 svc.resume,
                 lambda: svc.add_replica(0),
                 svc.rebalance):
        with pytest.raises(RuntimeError) as ei:
            call()
        assert ei.value.__cause__ is boom


def test_pump_death_unblocks_concurrent_waiters(monkeypatch):
    """_notify_everyone + _fail_admin: threads parked in result(), drain()
    and _run_admin() (all three condition classes) all wake with the
    chained error when the pump dies mid-wait."""
    t, fs = _mixed_table(n=1400)
    # the injected delay stalls the pump INSIDE its first launch, giving
    # all three waiter classes time to park before the pump's next tick
    inj = FaultInjector().delay_launches(0.5, 1)
    svc = FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                         buckets=(64,), coalesce=1, faults=inj)
    errs: dict[str, BaseException] = {}

    def waiter(name, fn):
        try:
            fn()
        except BaseException as e:
            errs[name] = e
    boom = RuntimeError("pump infrastructure fault")

    def die():
        raise boom
    tk = svc.submit(np.arange(8, 16))              # pump enters the delay
    threads = [threading.Thread(target=waiter, args=("result",
                                lambda: svc.result(tk))),
               threading.Thread(target=waiter, args=("drain", svc.drain)),
               threading.Thread(target=waiter, args=("admin",
                                lambda: svc.add_replica(0)))]
    for th in threads:
        th.start()
    time.sleep(0.1)                                # let them all park
    # the pump's next tick top runs _drain_admin — and dies there, with
    # the admin request still queued (_fail_admin must unblock it)
    monkeypatch.setattr(svc, "_drain_admin", die)
    for th in threads:
        th.join(timeout=20)
    assert not any(th.is_alive() for th in threads)
    assert set(errs) == {"result", "drain", "admin"}
    for e in errs.values():
        assert e.__cause__ is boom or e is boom


# -- device-loss recovery ------------------------------------------------------------
def test_device_loss_serves_via_host_gather():
    """Killing EVERY serving device must not lose a single ticket: each
    shard's streams get evicted as their device's DeviceDown arrives, and
    with no survivor to rebuild on the pump serves the orphaned shards
    from the host packed words — bit-exact, availability 1.0. (Tier-1's
    single-device run reaches this with one kill; the 4-device CI lane
    walks the evict -> rebuild -> re-evict chain until the pool is gone.)"""
    import jax
    t, fs = _mixed_table()
    rng = np.random.default_rng(17)
    requests = [rng.integers(0, 3000, rng.integers(8, 64))
                for _ in range(12)]
    requests += [np.arange(700 * s, 700 * s + 48) for s in range(4)]
    want = _reference(t, fs, requests)
    inj = FaultInjector()
    # retries cover the worst chain: a group re-placed onto another dead
    # device once per pool member before its shard goes host-served
    pol = FaultPolicy(max_retries=8, backoff_s=0.001, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)   # warm
        for d in jax.devices():
            inj.kill_device(d)
        tickets = [svc.submit(r) for r in requests]
        got = [svc.result(tk, timeout=120) for tk in tickets]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        st = svc.throughput_stats(1.0)
        assert st["availability"] == 1.0
        assert st["failed_tickets"] == 0
        assert st["devices_lost"] >= 1
        assert st["host_gathers"] > 0
        # evicted streams surrendered their breaker entries: the table
        # only holds tokens of streams still in the shard set
        live = {ex.stream_token
                for s in range(svc.n_shards)
                for ex in svc._sharded_ex.stream_executors(s)}
        assert set(svc._breakers) <= live


def test_device_loss_rebuilds_shard_on_survivor():
    """With a healthy device left in the pool, a dead device's shards are
    REBUILT there from the host packed words (version-keyed re-put): the
    miss window is host-served, the rebuild lands automatically (pump
    policy, no admin call), and post-recovery serving is bit-exact on
    device again."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (CI forces a 4-device host "
                    "platform)")
    t, fs = _mixed_table()
    inj = FaultInjector()
    pol = FaultPolicy(max_retries=8, backoff_s=0.001, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)   # warm
        dead = svc._sharded_ex.devices[0]
        inj.kill_device(dead)
        rows = np.arange(8, 56)
        np.testing.assert_array_equal(
            svc.result(svc.submit(rows), timeout=60),
            _reference(t, fs, [rows])[0])
        deadline = time.perf_counter() + 30
        while svc.stats["recoveries"] == 0 and \
                time.perf_counter() < deadline:
            time.sleep(0.005)
        st = dict(svc.stats)
        assert st["devices_lost"] == 1
        assert st["recoveries"] >= 1
        assert svc._sharded_ex.devices[0] is not dead
        launches0 = st["launches"]
        again = np.arange(64, 128)
        np.testing.assert_array_equal(
            svc.result(svc.submit(again), timeout=60),
            _reference(t, fs, [again])[0])
        assert svc.stats["launches"] > launches0   # device path is back
        assert svc.throughput_stats(1.0)["availability"] == 1.0


# -- supervised pump restart ---------------------------------------------------------
def test_pump_restart_survives_infrastructure_crash(monkeypatch):
    """ONE pump-infrastructure exception no longer poisons the service:
    the supervisor restarts the pump with the ledger intact, queued and
    re-enqueued work completes bit-exact, and only the restart budget
    separates this from the terminal path the _dying_service tests pin."""
    t, fs = _mixed_table()
    rng = np.random.default_rng(23)
    requests = [rng.integers(0, 3000, rng.integers(8, 64))
                for _ in range(10)]
    want = _reference(t, fs, requests)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1) as svc:
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)   # warm
        orig = svc._pick_action
        state = {"fired": False}

        def crash_once():
            if not state["fired"]:
                state["fired"] = True
                raise RuntimeError("injected pump-infrastructure crash")
            return orig()
        monkeypatch.setattr(svc, "_pick_action", crash_once)
        tickets = [svc.submit(r) for r in requests]
        got = [svc.result(tk, timeout=60) for tk in tickets]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert svc.stats["pump_restarts"] == 1
        assert svc.stats["failed_tickets"] == 0
        # and the restarted pump is a full citizen: drain/collect work
        svc.drain(timeout=60)


def test_pump_restart_reenqueues_partially_retired_flight(monkeypatch):
    """A crash INSIDE _retire (after the flight left the launch queue)
    must not strand its chunks: the retire journal re-enqueues exactly
    the unretired remainder, the relaunch retires it, and every ticket
    resolves bit-exact — the restart is invisible to clients."""
    t, fs = _mixed_table()
    rng = np.random.default_rng(29)
    requests = [rng.integers(0, 3000, rng.integers(8, 64))
                for _ in range(8)]
    want = _reference(t, fs, requests)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1) as svc:
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)   # warm
        orig = svc._retire
        state = {"fired": False}

        def crash_once(arr, parts):
            if not state["fired"]:
                state["fired"] = True
                raise RuntimeError("injected crash mid-retire")
            return orig(arr, parts)
        monkeypatch.setattr(svc, "_retire", crash_once)
        tickets = [svc.submit(r) for r in requests]
        got = [svc.result(tk, timeout=60) for tk in tickets]
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        assert svc.stats["pump_restarts"] == 1
        assert svc.stats["failed_tickets"] == 0


# -- speculative hedged launches -----------------------------------------------------
def test_hedged_launch_beats_stalled_primary():
    """A launch whose retire wait crosses the hedge cutoff gets a
    duplicate on the shard's other healthy stream; the duplicate retires
    FIRST (the primary is stalled), resolves the tickets bit-exact, and
    the straggler's eventual buffer is discarded without double-counting.
    Latency: the ticket completes in ~the hedge cutoff, far under the
    stall."""
    t, fs = _mixed_table()
    inj = FaultInjector()
    pol = FaultPolicy(hedge=True, hedge_min_s=0.02, hedge_factor=2.0,
                      straggler_min_s=10.0, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        svc.add_replica(0)
        rows = np.arange(0, 64)
        for _ in range(10):                        # warm EWMA past warmup
            svc.result(svc.submit(rows), timeout=60)
        completed0 = svc.stats["completed"]
        inj.stall_launches(0.6, 1, shard=0)        # next primary launch
        t0 = time.perf_counter()
        out = svc.result(svc.submit(rows), timeout=60)
        dt = time.perf_counter() - t0
        np.testing.assert_array_equal(out, _reference(t, fs, [rows])[0])
        st = dict(svc.stats)
        assert st["hedges"] >= 1
        assert st["hedge_wins"] >= 1
        assert dt < 0.5                            # did not ride the stall
        assert st["completed"] == completed0 + 1   # no double-count
        assert st["failed_tickets"] == 0


def test_hedged_one_lane_interactive_launch_retires_bit_exact():
    """The stalled-primary setup with an ``interactive`` submit on a
    4-deep service: the class launches one lane, not four, and its hedged
    duplicate is launched at the same width, so the parts' row offsets
    hold and the duplicate's buffer resolves the ticket bit-exact."""
    t, fs = _mixed_table()
    inj = FaultInjector()
    pol = FaultPolicy(hedge=True, hedge_min_s=0.02, hedge_factor=2.0,
                      straggler_min_s=10.0, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=4, faults=inj,
                        fault_policy=pol,
                        classes=default_classes()) as svc:
        svc.add_replica(0)
        rows = np.arange(5, 52)
        for _ in range(10):                        # warm EWMA past warmup
            svc.result(svc.submit(rows, klass="interactive"), timeout=60)
        st0 = dict(svc.stats)
        assert st0["launched_rows"] == 64 * st0["launches"]   # one lane
        inj.stall_launches(0.6, 1, shard=0)        # next primary launch
        t0 = time.perf_counter()
        out = svc.result(svc.submit(rows, klass="interactive"), timeout=60)
        dt = time.perf_counter() - t0
        np.testing.assert_array_equal(out, _reference(t, fs, [rows])[0])
        st = dict(svc.stats)
        assert st["hedges"] >= 1
        assert st["hedge_wins"] >= 1
        assert dt < 0.5                            # did not ride the stall
        assert st["completed"] == st0["completed"] + 1
        assert st["launched_rows"] - st0["launched_rows"] == 64
        assert st["failed_tickets"] == 0


def test_no_hedge_policy_rides_out_the_stall():
    """hedge=False is the control: the same stall is simply waited out
    (that contrast is what the hedged serving benchmark measures)."""
    t, fs = _mixed_table()
    inj = FaultInjector()
    pol = FaultPolicy(hedge=False, straggler_min_s=10.0, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        svc.add_replica(0)
        rows = np.arange(0, 64)
        for _ in range(10):
            svc.result(svc.submit(rows), timeout=60)
        inj.stall_launches(0.3, 1, shard=0)
        t0 = time.perf_counter()
        out = svc.result(svc.submit(rows), timeout=60)
        dt = time.perf_counter() - t0
        np.testing.assert_array_equal(out, _reference(t, fs, [rows])[0])
        assert dt >= 0.28                          # rode the stall
        assert svc.stats["hedges"] == 0


# -- refresh() racing stream loss ----------------------------------------------------
def test_replica_lost_between_refresh_and_reput_resyncs_lazily():
    """A stream that fails BETWEEN plan.refresh() and its version-keyed
    re-put must not serve stale words: the failed launch fails over to a
    stream that re-puts first (bit-exact vs the refreshed reference), and
    once the faulted stream heals, its own next launch performs the lazy
    re-sync — also bit-exact."""
    t, fs = _mixed_table(n=1400, imcu_rows=700)
    plan_p = FeaturePlan(t, fs, packed=True)
    plan_i = FeaturePlan(t, fs)                    # refreshed ground truth
    ref_ex = FeatureExecutor(plan_i)
    pol = FaultPolicy(max_retries=4, backoff_s=0.001, breaker_fails=100)
    inj = FaultInjector()
    with FeatureService(plan_p, sharded=True, buckets=(64,), coalesce=1,
                        faults=inj, fault_policy=pol) as svc:
        svc.add_replica(0)
        rows = np.arange(8, 56)
        for _ in range(4):                         # both streams resident
            svc.result(svc.submit(rows), timeout=60)
        new = {"age": t["age"].dictionary.add_rows(np.array([150])),
               "state": t["state"].dictionary.add_rows(np.array(["TX"])),
               "income": t["income"].dictionary.add_rows(
                   np.array([1_234_000]))}
        plan_p.refresh(new)
        plan_i.refresh(new)
        # the next shard-0 launch dies before it can re-put its words
        inj.fail_launches(1, shard=0)
        want = np.asarray(ref_ex.batch(rows))
        np.testing.assert_array_equal(
            svc.result(svc.submit(rows), timeout=60), want)
        assert svc.stats["failovers"] > 0
        # the healed stream's own next launches lazily re-sync: serve
        # enough that round-robin touches BOTH streams post-refresh
        for _ in range(4):
            np.testing.assert_array_equal(
                svc.result(svc.submit(rows), timeout=60), want)
        assert svc.stats["failed_tickets"] == 0


# -- breaker hygiene (regression: table leak + gauge) --------------------------------
def test_drop_replica_discards_breaker_entry():
    """_breakers is keyed by stream token and cleaned on drop: dropping a
    replica removes exactly its entry (the old id()-keyed table leaked
    one entry per dropped stream and could alias a recycled id onto a
    NEW stream's state)."""
    t, fs = _mixed_table(n=1400, imcu_rows=700)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1) as svc:
        svc.add_replica(0)
        dropped_tok = svc._sharded_ex.replicas[0][-1].stream_token
        for _ in range(4):                         # traffic on both streams
            svc.result(svc.submit(np.arange(0, 32)), timeout=60)
        assert dropped_tok in svc._breakers
        svc.drop_replica(0)
        assert dropped_tok not in svc._breakers
        live = {ex.stream_token
                for s in range(svc.n_shards)
                for ex in svc._sharded_ex.stream_executors(s)}
        assert set(svc._breakers) <= live
        # and the drop never underflows the unhealthy gauge
        assert svc.stats["unhealthy_shards"] == 0


def test_unhealthy_shards_is_a_gauge():
    """unhealthy_shards DECREMENTS when the probe closes a breaker — it
    reports streams unhealthy NOW, not trips ever."""
    t, fs = _mixed_table()
    inj = FaultInjector().fail_launches(2, shard=0, stream=0)
    pol = FaultPolicy(max_retries=5, backoff_s=0.001, breaker_fails=2,
                      breaker_cooldown_s=0.05)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64,), coalesce=1, faults=inj,
                        fault_policy=pol) as svc:
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)
        assert svc.stats["unhealthy_shards"] == 1  # open: gauge holds
        time.sleep(0.06)                           # cooldown -> half-open
        svc.result(svc.submit(np.arange(0, 32)), timeout=60)  # probe
        assert svc.stats["unhealthy_shards"] == 0  # closed: gauge returns
        b = svc._breakers[svc._sharded_ex.executors[0].stream_token]
        assert b.opened == 1 and b.fails == 0


# -- seeded randomized sweep (nightly sets CHAOS_SWEEP_SEEDS high) -------------------
@pytest.mark.parametrize("seed",
                         range(int(os.environ.get("CHAOS_SWEEP_SEEDS", 2))))
def test_chaos_random_sweep_with_replicas_never_loses_a_ticket(seed):
    """Random faults + delays (seeded) against a fully replicated shard
    set: with a healthy stream always available and retries > expected
    consecutive faults, EVERY ticket must complete bit-exact."""
    t, fs = _mixed_table(n=2100, imcu_rows=700, seed=seed)
    rng = np.random.default_rng(100 + seed)
    requests = [rng.integers(0, 2100, rng.integers(4, 80))
                for _ in range(30)]
    want = _reference(t, fs, requests)
    inj = FaultInjector(seed=seed).random_faults(p_fail=0.25, p_delay=0.05,
                                                 delay_s=0.01)
    pol = FaultPolicy(max_retries=6, backoff_s=0.001, breaker_fails=4,
                      breaker_cooldown_s=0.02)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64, 256), faults=inj,
                        fault_policy=pol) as svc:
        for s in range(svc.n_shards):
            svc.add_replica(s)
        tickets = [svc.submit(r) for r in requests]
        got = [svc.result(tk, timeout=120) for tk in tickets]
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    st = svc.throughput_stats(1.0)
    assert st["availability"] == 1.0
    assert inj.faults_injected > 0


@pytest.mark.parametrize("seed",
                         range(int(os.environ.get("CHAOS_SWEEP_SEEDS", 2))))
def test_chaos_sweep_device_loss_mid_traffic(seed):
    """Random faults PLUS a device killed mid-run: the first wave serves
    normally, then a device (seed-chosen) dies and the second wave rides
    eviction + rebuild-or-host-gather. No ticket is ever lost and every
    result stays bit-exact — the device-loss acceptance bar under the
    same randomized schedule the nightly lane widens."""
    import jax
    t, fs = _mixed_table(n=2100, imcu_rows=700, seed=seed)
    rng = np.random.default_rng(300 + seed)
    wave1 = [rng.integers(0, 2100, rng.integers(4, 80)) for _ in range(10)]
    wave2 = [rng.integers(0, 2100, rng.integers(4, 80)) for _ in range(15)]
    want1 = _reference(t, fs, wave1)
    want2 = _reference(t, fs, wave2)
    inj = FaultInjector(seed=seed).random_faults(p_fail=0.1, p_delay=0.05,
                                                 delay_s=0.01)
    pol = FaultPolicy(max_retries=8, backoff_s=0.001, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64, 256), faults=inj,
                        fault_policy=pol) as svc:
        for g, w in zip((svc.result(svc.submit(r), timeout=120)
                         for r in wave1), want1):
            np.testing.assert_array_equal(g, w)
        # kill a device that actually HOLDS a shard (on a wide mesh
        # some devices are empty and their loss is unobservable)
        devs = svc._sharded_ex.devices
        inj.kill_device(devs[seed % len(devs)])
        tickets = [svc.submit(r) for r in wave2]
        got = [svc.result(tk, timeout=120) for tk in tickets]
    for g, w in zip(got, want2):
        np.testing.assert_array_equal(g, w)
    st = svc.throughput_stats(1.0)
    assert st["availability"] == 1.0
    assert st["failed_tickets"] == 0
    assert st["devices_lost"] >= 1


@pytest.mark.parametrize("seed",
                         range(int(os.environ.get("CHAOS_SWEEP_SEEDS", 2))))
def test_chaos_tier_transitions_with_device_loss(seed):
    """Tier transitions racing faults AND a device kill: shards are demoted
    down the ladder (host-warm, RLE-cold) mid-traffic, a seed-chosen device
    dies, and promotions are requested while launches still carry injected
    faults. Demoted shards must keep host-serving through the loss (they
    skip rebuild entirely), a promotion whose home device died rebuilds on
    a survivor (or stays warm when none exists — a 1-device process), and
    every ticket lands bit-exact with availability 1.0."""
    import jax
    t, fs = _mixed_table(n=2100, imcu_rows=700, seed=seed)
    rng = np.random.default_rng(700 + seed)
    wave1 = [rng.integers(0, 2100, rng.integers(4, 80)) for _ in range(8)]
    wave2 = [rng.integers(0, 2100, rng.integers(4, 80)) for _ in range(15)]
    want1 = _reference(t, fs, wave1)
    want2 = _reference(t, fs, wave2)
    inj = FaultInjector(seed=seed).random_faults(p_fail=0.1, p_delay=0.05,
                                                 delay_s=0.01)
    pol = FaultPolicy(max_retries=8, backoff_s=0.001, breaker_fails=100)
    with FeatureService(FeaturePlan(t, fs, packed=True), sharded=True,
                        buckets=(64, 256), faults=inj,
                        fault_policy=pol) as svc:
        for g, w in zip((svc.result(svc.submit(r), timeout=120)
                         for r in wave1), want1):
            np.testing.assert_array_equal(g, w)
        svc.demote(0, "cold")                   # closed shard: runs only
        svc.demote(1, "warm")
        assert svc.tiers[:2] == ["cold", "warm"]
        # kill a device that actually HOLDS a shard (on a wide mesh
        # some devices are empty and their loss is unobservable)
        devs = svc._sharded_ex.devices
        inj.kill_device(devs[seed % len(devs)])
        tickets = [svc.submit(r) for r in wave2]
        # promotions race the faulted/killed traffic on the pump
        svc.promote(1)
        svc.promote(0)
        got = [svc.result(tk, timeout=120) for tk in tickets]
        for g, w in zip(got, want2):
            np.testing.assert_array_equal(g, w)
        # post-loss steady state: every tier still serves bit-exact
        again = rng.integers(0, 2100, 200)
        np.testing.assert_array_equal(
            svc.result(svc.submit(again), timeout=120),
            _reference(t, fs, [again])[0])
        assert (svc.stats["tier_hot"] + svc.stats["tier_warm"]
                + svc.stats["tier_cold"]) == svc.n_shards
    st = svc.throughput_stats(1.0)
    assert st["availability"] == 1.0
    assert st["failed_tickets"] == 0
    assert st["devices_lost"] >= 1
    assert svc.stats["demotions"] >= 2
