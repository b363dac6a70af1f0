"""FeatureService walkthrough: pump-driven, coalescing ADV feature serving.

Serving architecture — every request flows through the same pipeline::

    request --> bucket --> unified coalescer --> pump --> launch
    submit()    chunk to    ONE queue; up to      background thread: the
    returns a   static      `coalesce` chunks     ONLY dispatcher. Keeps
    ticket      bucket      of one bucket shape   `prefetch` launches in
                shapes      share a launch        flight, retires oldest

``submit`` only enqueues; ``poll``/``result``/``drain`` only inspect or
wait. Over a packed plan (``FeaturePlan(packed=True)``) the word streams
are device-resident and EVERY chunk — word-aligned scan range or arbitrary
row set — is served by the indexed gather kernel, which computes word index
+ bit offset on the device. ``stats['bytes_h2d']`` therefore reports INDEX
bytes (4B x padded rows, independent of column count): random requests ship
indices, never codes. int32 plans still ship (C, bucket) code slices.

Mesh-sharded serving (``sharded=True`` over a packed plan): the table's
IMCU partitions become per-shard RESIDENT word-stream slices, each
committed to its own mesh device (``jax.device_put`` placement via
``repro.distributed.sharding.serve_devices`` — round-robin when shards and
devices differ in count). A request's rows are bucketed by owning IMCU at
submit; whole-shard requests (the clustered per-user pattern) route with
two scalar bisects and no per-row work. One multiplexing pump keeps
``prefetch`` launches in flight PER SHARD and coalesces each shard's
same-bucket chunks into single launches, so independent shards' gathers
run concurrently on their own devices — compute moves to the shard that
owns the data, never shard bytes to one compute device. ``linger_us``
bounds how long a pump holds a partial coalescing group open under light
load (fuller groups for a bounded latency); ``drain()`` force-flushes
lingering groups. Per-shard attribution: ``stats['shard_launches'/
'shard_bytes_h2d']`` and ``plan.stats['per_shard']`` roll up into totals.

Adaptive shard management (the paper's feedback cycle, applied to layout):
a mesh service's shard set is no longer frozen at plan-build time. A load
monitor fed by the per-shard stats deltas (request-rate EWMA over
``stats['shard_batches']``) drives two policies, automatically every
``rebalance_every`` launches or on demand via ``service.rebalance()``:

- hot-key skew -> **replicate**: when one shard's request rate runs
  ``hot_factor`` x the mean, its resident word stream is committed to the
  least-loaded device too and the pump round-robins that shard's launches
  across the copies (read fan-out; every copy re-syncs from the plan's
  versioned words after a refresh, so writes invalidate replicas for
  free). Cold shards shed their replicas again.
- streaming growth -> **re-shard**: appends extend only the open tail
  shard; past ``row_budget`` rows the tail splits at a word-aligned cut,
  the new shard's slice moves to an under-loaded device, and the routing
  table swaps atomically — queued chunks are re-routed (split when they
  straddle the cut) without dropping or reordering a single ticket.

Tiered residency (``hbm_budget_bytes``): the same monitor extends from
"replicate hot" to a full residency ladder, so the table no longer has
to fit on device. Every shard is **hot** (device-resident packed words),
**warm** (host packed words, served by the host-gather slow path — a
small thread pool fans wide gathers out when the host has spare cores)
or **cold** (RLE runs only, ~bits/32 of the word bytes on run-heavy
columns); a per-device byte budget caps what stays hot. Budget pressure
demotes the coldest-EWMA residents, warm shards idle for ``cold_after``
monitor ticks compress to runs, and traffic on an off-device shard
triggers ASYNC promotion on the pump (cold rehydrates first; a full
device displaces colder residents). Every miss serves bit-exact through
the host path while the promotion races — availability never dips, and
tables many times the device budget serve near hot-tier throughput
under skewed access.

Builds a columnar table, compiles a FeaturePlan (device-resident fused ADV
tables), then serves featurization requests ten ways:

1. request queue with tickets (submit / result),
2. arbitrary-row ("millions of users") lookups over a packed plan — the
   coalescer folds them into single index-only launches,
3. mesh-sharded serving: per-IMCU resident shards + routed pump launches
   (run under XLA_FLAGS=--xla_force_host_platform_device_count=4 to see
   true multi-device placement on CPU),
4. skewed traffic -> monitor -> replicate -> re-shard: the adaptive cycle
   above, driven by Zipf-hot lookups and a streaming append,
5. predicate-filtered serving (query pushdown): ``submit(where=...)``
   evaluates dictionary-code predicates directly on the resident packed
   words (scan -> compact -> gather, one device pipeline — no decoded
   code stream, no host round trip), plus dict-aware masked aggregates
   (``count_where`` / ``groupby_where`` / ``agg_where``),
6. failure handling: a chaos-injected launch fault stream on one shard —
   retries + replica failover keep every ticket completing; the breaker
   marks the sick stream, ``rebalance()`` re-replicates around it, and
   when NO replica exists only the faulted tickets resolve to typed
   ``ServeError``s (the service keeps serving; ``deadline_ms``/
   ``timeout=`` bound every wait). Phase 2 extends this past stream
   faults: a killed DEVICE (6b) has its streams evicted, missed shards
   host-gather-served, and each orphan rebuilt on a survivor from the
   host packed words; a STALLED launch (6c) is raced by a speculative
   duplicate on another healthy stream once its wait crosses the hedge
   cutoff — first buffer ready wins, the straggler is discarded. A pump
   infrastructure crash is supervised too: the pump restarts with the
   ledger intact (``FaultPolicy.pump_restarts`` bounds the budget),
7. streaming double-buffered iteration (serve_stream),
8. a streaming insert followed by an incremental plan refresh — only the
   columns whose dictionaries changed are re-put on device; appended rows
   extend the open-ended LAST shard, so sharded services keep serving,
9. tiered residency: the hot/warm/cold shard ladder above, driven by an
   ``hbm_budget_bytes`` cap half the table's size — explicit demotion
   down to RLE runs, a bit-exact cold miss, and async promotion back,
10. the production front door: a ``FeatureFrontend`` over per-tenant
    request classes (``interactive``/``batch``/``background``) — the
    pump schedules launches by class priority with anti-starvation
    aging and per-class coalescing/linger, admission is bounded per
    class (``max_inflight`` + ``queue_depth``, then a typed
    ``Overloaded`` with a retry-after hint), and per-class streaming
    latency histograms feed the stats/SLO endpoint (unbiased p99s —
    every completed ticket, not a sliding sample window).

Run:  PYTHONPATH=src python examples/feature_service.py
"""
import time

import numpy as np

from repro.columnar import Table
from repro.compile_cache import enable_compile_cache
from repro.core import FeatureSet, FeaturePlan
from repro.serve import FeatureFrontend, FeatureService, Overloaded


def main() -> None:
    rng = np.random.default_rng(0)
    n = 100_000
    table = Table.from_data({
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
    }, imcu_rows=1 << 15)
    features = (FeatureSet()
                .add("age", "zscore")
                .add("age", "bucketize", boundaries=(30.0, 45.0, 65.0))
                .add("state", "onehot")
                .add("income", "minmax"))
    plan = FeaturePlan(table, features)
    print(f"plan: {len(plan.plans)} columns, out_dim={plan.out_dim}, "
          f"resident_tables={plan.bytes_resident_tables()}B, "
          f"imcus={table['age'].n_imcus}")

    # 1. ticketed request queue (double-buffered dispatch under the hood)
    svc = FeatureService(plan, prefetch=2)
    t0 = time.perf_counter()
    tickets = [svc.submit(rng.integers(0, n, 512)) for _ in range(64)]
    feats = svc.result(tickets[0])
    svc.drain()
    wall = time.perf_counter() - t0
    print(f"served 64 requests: first result {feats.shape}, "
          f"{svc.throughput_stats(wall)['rows_per_s']:.0f} rows/s")

    # 2. packed plan: arbitrary-row requests ship ONLY indices — the pump
    # coalesces them and the device computes word/bit offsets itself
    with FeatureService(FeaturePlan(table, features, packed=True),
                        prefetch=2, buckets=(512,)) as svcp:
        tickets = [svcp.submit(rng.integers(0, n, 512)) for _ in range(64)]
        svcp.drain()
        st = svcp.stats
        print(f"packed random serving: {st['launches']} launches for "
              f"{st['requests']} requests, h2d={st['bytes_h2d']}B "
              f"(indices only, ~4B/row x {svcp.coalesce} coalesced)")

    # 3. mesh-sharded serving: per-IMCU resident word-stream shards, each
    # on its own device; rows route to their owning shard and each shard's
    # launches coalesce independently (linger trades <=1ms for fuller
    # groups). Requests here are clustered per-user blocks — the whole
    # request lands on one shard, so routing is two bisects.
    from repro.distributed.sharding import serve_mesh
    mesh = serve_mesh()                # 1-D ('shard',) mesh over the devices
    plan_mesh = FeaturePlan(table, features, packed=True)
    with FeatureService(plan_mesh, sharded=True, buckets=(512,),
                        coalesce=8, linger_us=1000,
                        devices=mesh.devices.tolist()) as svcs:
        for s in rng.integers(0, (n - 512) // 32, 64) * 32:
            svcs.submit(np.arange(s, s + 512))
        svcs.drain()
        st = svcs.stats
        print(f"mesh serving: {svcs.n_shards} shards over "
              f"{mesh.shape['shard']} mesh device(s), "
              f"launches per shard={st['shard_launches']}, "
              f"h2d per shard={st['shard_bytes_h2d']}B (indices only); "
              f"plan per-shard words_put="
              f"{[s['words_put'] for s in plan_mesh.stats['per_shard']]}")

    # 4. adaptive shard management: skewed traffic -> monitor -> replicate
    # -> re-shard. Zipf-hot lookups concentrate on shard 0; the monitor's
    # request-rate EWMA flags it and fans reads out over a replica. A
    # streaming append then pushes the open tail past its row budget and
    # the next rebalance splits it — all while requests keep flowing.
    plan_ad = FeaturePlan(table, features, packed=True)
    with FeatureService(plan_ad, sharded=True, buckets=(512,), coalesce=8,
                        linger_us=1000, rebalance_every=6,
                        row_budget=1 << 15, hot_factor=2.0,
                        max_replicas=2) as svca:
        hot = rng.integers(0, (1 << 15) // 32 - 16, 96) * 32   # shard 0
        for s in hot:
            svca.submit(np.arange(s, s + 512))
        svca.drain()                       # pump ticks the monitor en route
        print(f"skew: monitor replicated hot shard 0 -> "
              f"{svca.replicas} replicas/shard "
              f"(EWMA={[round(e, 1) for e in svca.monitor_ewma]})")
        m = 1 << 15
        grow = {c: table[c].dictionary.add_rows(
            table[c].dictionary.values[
                rng.integers(0, table[c].dictionary.cardinality, m)])
            for c in plan_ad.columns}
        plan_ad.refresh(grow)              # tail now exceeds row_budget
        actions = svca.rebalance()
        print(f"growth: tail re-shard at {actions['split']}; now "
              f"{svca.n_shards} shards, starts={svca.shard_starts}")
        tail = svca.submit(np.arange(plan_ad.n_rows - 64, plan_ad.n_rows))
        print(f"fresh tail serves: {svca.result(tail).shape}, stats: "
              f"splits={svca.stats['shard_splits']}, "
              f"replicas_added={svca.stats['replicas_added']}")

    # 5. query pushdown: serve features WHERE ... as ONE device pipeline.
    # The predicate compiles to code-space terms (equality/ranges ->
    # [lo, hi] compares, IN-sets -> a K-entry LUT probe), the scan
    # evaluates them on the resident packed words without decoding a code
    # stream, the selection compacts to row indices on device, and those
    # indices feed the same packed gather every other request uses. Only
    # the match count (one scalar) and the features come back to the host.
    from repro.columnar import query as Q
    pred = Q.isin("state", [3, 7, 11]) & Q.gt("age", 60)
    with FeatureService(FeaturePlan(table, features, packed=True),
                        sharded=True, buckets=(512,), coalesce=8,
                        linger_us=1000) as svcq:
        tq = svcq.submit(where=pred)       # sharded: each shard scans and
        feats = svcq.result(tq)            # serves its own matches locally
        print(f"filtered serving: {pred!r} -> {feats.shape[0]} rows "
              f"({svcq.stats['filtered_requests']} filtered request(s), "
              f"features {feats.shape})")
        # dict-aware masked aggregates: a masked per-code histogram over
        # the resident words, then K-entry tail math — COUNT/SUM/MEAN
        # under a predicate never touch an N-row value stream
        vals, counts = svcq.groupby_where("state", Q.gt("age", 60))
        top = vals[np.argmax(counts)]
        print(f"aggregates: count={svcq.count_where(pred)}, "
              f"mean(income | pred)={svcq.agg_where(pred, 'income', 'mean'):.0f}, "
              f"busiest state over 60: {top} ({counts.max()} rows)")

    # 6. failure handling: inject faults -> observe failover -> recover.
    # The FaultInjector scripts deterministic launch faults on the pump's
    # dispatch path (exactly where a real device error would land). With a
    # replica resident, retries fail over to it and NOTHING is lost; the
    # struck stream's circuit breaker marks the shard unhealthy and
    # rebalance() re-replicates around it.
    from repro.serve import FaultInjector, FaultPolicy, ServeError
    inj = FaultInjector().fail_launches(6, shard=0, stream=0)
    pol = FaultPolicy(max_retries=3, backoff_s=0.005,
                      breaker_fails=3, breaker_cooldown_s=0.2)
    with FeatureService(FeaturePlan(table, features, packed=True),
                        sharded=True, buckets=(512,), coalesce=1,
                        faults=inj, fault_policy=pol,
                        max_replicas=2) as svcf:
        svcf.add_replica(0)                # the failover target
        hot = [svcf.submit(np.arange(s, s + 512))
               for s in rng.integers(0, (1 << 15) // 32 - 16, 24) * 32]
        ok = sum(svcf.result(t).shape[0] == 512 for t in hot)
        st = svcf.throughput_stats(1.0)
        print(f"chaos: {inj.faults_injected} injected faults -> {ok}/24 "
              f"tickets served (availability={st['availability']:.2f}), "
              f"retries={st['retries']}, failovers={st['failovers']}, "
              f"unhealthy={svcf.unhealthy}")
        if svcf.unhealthy:                 # monitor re-replicates around it
            acts = svcf.rebalance()
            print(f"recovery: replicated={acts['replicated']} "
                  f"failover_replicated={acts['failover_replicated']}, "
                  f"replicas={svcf.replicas}")
    # without replicas, a persistent fault fails ONLY its own tickets —
    # each resolves to a typed ServeError; the service keeps serving
    # (3 faults = 1 launch + 2 retries: the shard-0 ticket exhausts them,
    # then the fault heals and the closing submit proves recovery)
    inj2 = FaultInjector().fail_launches(3, shard=0)
    with FeatureService(FeaturePlan(table, features, packed=True),
                        sharded=True, buckets=(512,), coalesce=8,
                        faults=inj2,
                        fault_policy=FaultPolicy(max_retries=2)) as svcn:
        t_bad = svcn.submit(np.arange(0, 512), deadline_ms=30_000)
        t_ok = svcn.submit(np.arange(1 << 15, (1 << 15) + 512))
        outcome = {}
        for name, t in (("shard0", t_bad), ("shard1", t_ok)):
            try:
                outcome[name] = f"served {svcn.result(t, timeout=30).shape}"
            except ServeError as e:
                outcome[name] = (f"failed after {e.attempts} attempts "
                                 f"({type(e).__name__})")
        print(f"isolation: {outcome} — failed_tickets="
              f"{svcn.stats['failed_tickets']}, service still accepting: "
              f"{svcn.result(svcn.submit(np.arange(64, 128))).shape}")

    # 6b. device-loss recovery: kill a device -> evict -> rebuild -> resume.
    # One DeviceDown (injected here; a real runtime raises its own when an
    # accelerator falls off the bus) marks the device dead. Its resident
    # streams are evicted; the missed shards serve from the HOST packed
    # words meanwhile (bit-exact, slower); the pump rebuilds each orphaned
    # shard on a surviving device via the version-keyed re-put and device
    # serving resumes. With only one device in the pool (the default CPU
    # run) there is no survivor — host gathers carry the whole service,
    # availability still 1.0.
    import jax
    from repro.serve import DeviceDown  # noqa: F401  (the class one kills)
    inj3 = FaultInjector()
    with FeatureService(FeaturePlan(table, features, packed=True),
                        sharded=True, buckets=(512,), coalesce=8,
                        faults=inj3,
                        fault_policy=FaultPolicy(max_retries=8)) as svcd:
        svcd.result(svcd.submit(np.arange(0, 512)))          # warm
        dead = svcd._sharded_ex.devices[0]                   # shard 0 owner
        inj3.kill_device(dead)
        served = [svcd.result(svcd.submit(np.arange(s, s + 512)))
                  for s in (0, 1 << 15)]                     # dead + alive
        time.sleep(0.05)                   # give the pump its rebuild tick
        st = svcd.stats
        mode = ("rebuilt on a survivor" if st["recoveries"]
                else "host-gather fallback (no surviving device)")
        print(f"device loss: killed {dead} -> devices_lost="
              f"{st['devices_lost']}, {mode}; host_gathers="
              f"{st['host_gathers']}, recoveries={st['recoveries']}, "
              f"served {[f.shape[0] for f in served]} rows through it, "
              f"failed_tickets={st['failed_tickets']}")

    # 6c. speculative hedged launches: the straggler timeline. A launch
    # whose retire wait crosses max(hedge_min_s, hedge_factor x the
    # shard's EWMA round-trip mean) gets a DUPLICATE launch on another
    # healthy stream of the shard; first buffer ready resolves the
    # tickets, the loser is discarded (and struck). Timeline for the
    # stalled launch below (stall=80ms, cutoff~=5ms):
    #
    #   t=0     launch on primary      (injected stall: buffer late 80ms)
    #   t~=5ms  wait crosses cutoff -> hedge launch on the replica
    #   t~=6ms  replica buffer ready -> tickets retire (hedge_wins += 1)
    #   t=80ms  primary buffer ready -> discarded, no double count
    inj4 = FaultInjector()
    polh = FaultPolicy(hedge=True, hedge_min_s=0.005, hedge_factor=4.0,
                       breaker_fails=1 << 30, straggler_min_s=1e9)
    with FeatureService(FeaturePlan(table, features, packed=True),
                        sharded=True, buckets=(512,), coalesce=1,
                        faults=inj4, fault_policy=polh) as svch:
        svch.add_replica(0)                # the stream hedges land on
        for _ in range(8):                 # train the EWMA past warmup
            svch.result(svch.submit(np.arange(0, 512)))
        inj4.stall_launches(0.08, 1, shard=0)
        t0 = time.perf_counter()
        out = svch.result(svch.submit(np.arange(0, 512)), timeout=30)
        dt = time.perf_counter() - t0
        st = svch.stats
        print(f"hedging: stalled launch served {out.shape} in "
              f"{dt * 1e3:.1f}ms (stall was 80ms) — hedges={st['hedges']}, "
              f"hedge_wins={st['hedge_wins']}, completed={st['completed']}")

    # 7. streaming
    stream = svc.serve_stream(rng.integers(0, n, 256) for _ in range(8))
    for rows, out in stream:
        pass
    print(f"streamed 8 batches, last={out.shape}")

    # 8. streaming insert + incremental refresh
    new_codes = {
        "age": table["age"].dictionary.add_rows(np.array([101, 102])),
        "state": table["state"].dictionary.add_rows(np.array([7, 7])),
        "income": table["income"].dictionary.add_rows(np.array([999_000,
                                                                21_000])),
    }
    refreshed = plan.refresh(new_codes)
    print(f"insert refreshed {refreshed} column plan(s) "
          f"(stats={plan.stats}); n_rows={plan.n_rows}")
    tail = svc.submit(np.array([n, n + 1]))
    print("features for the inserted rows:\n", svc.result(tail))
    svc.shutdown()                     # join the pump thread when disposing

    # 9. tiered residency: a device byte budget HALF the table's resident
    # word bytes. Shards commit hot in order while they fit; the rest
    # start warm (host packed words). We then walk shard 0 down the
    # ladder by hand — 'warm' frees its device words, 'cold' additionally
    # compresses the host copy to RLE runs — serve a request through the
    # cold slow path (bit-exact; the pump may race an async promotion,
    # misses never wait for it), and promote it back (cold rehydrates
    # from runs first, then re-commits under the budget, displacing a
    # colder resident if the device is full).
    from repro.core import ShardedFeatureExecutor
    probe = ShardedFeatureExecutor(FeaturePlan(table, features, packed=True),
                                   hbm_budget_bytes=1)   # commits nothing:
    total = sum(e.stream_nbytes() for e in probe.executors)  # size the cap
    with FeatureService(FeaturePlan(table, features, packed=True),
                        sharded=True, buckets=(512,), coalesce=8,
                        linger_us=1000, rebalance_every=4, max_replicas=0,
                        hbm_budget_bytes=max(1, total // 2),
                        cold_after=3) as svct:
        print(f"tiers under a {total // 2}B budget (table={total}B): "
              f"{svct.tiers}, resident="
              f"{sum(svct.device_bytes().values())}B")
        freed = svct.demote(0, "warm")     # device words released
        svct.demote(0, "cold")             # host words -> RLE runs
        miss = svct.result(svct.submit(np.arange(0, 512)))
        print(f"cold shard 0 served {miss.shape} bit-exact "
              f"(freed {freed}B device; tier_misses="
              f"{svct.stats['tier_misses']})")
        ok = svct.promote(0)               # rehydrate + re-commit
        st = svct.stats
        print(f"promoted back: {ok}; tiers={svct.tiers}; "
              f"promotions={st['promotions']} demotions={st['demotions']} "
              f"rehydrations={st['rehydrations']}; resident="
              f"{sum(svct.device_bytes().values())}B <= {total // 2}B")

    # 10. the production front door. for_plan() builds the service with
    # the preset three-tier class ladder (interactive: priority 3,
    # singleton immediate launches, 5s deadline; batch: priority 2,
    # normal coalescing; background: priority 1, small admission window,
    # aged up so it drains but never starves anyone) and wraps it in the
    # admission-controlled FeatureFrontend. Tenants share the service;
    # classes bound what each can have outstanding.
    with FeatureFrontend.for_plan(FeaturePlan(table, features, packed=True),
                                  sharded=True, buckets=(512,),
                                  coalesce=8, linger_us=500) as fe:
        tickets = [fe.submit(rng.integers(0, n, 512), klass="batch",
                             tenant="analytics") for _ in range(12)]
        tickets += [fe.submit(np.arange(s, s + 512), klass="interactive",
                              tenant="app") for s in (0, 4096)]
        tickets.append(fe.submit(rng.integers(0, n, 512),
                                 klass="background", tenant="scavenger"))
        fe.collect()
        # overload: hold the pump and flood the background window — the
        # bound rejects with a typed Overloaded + retry-after hint
        # instead of growing an unbounded queue
        fe.service.pause()
        rejected, hint = 0, 0.0
        try:
            for _ in range(2048):
                fe.submit(np.arange(0, 64), klass="background",
                          tenant="scavenger")
        except Overloaded as e:
            rejected, hint = 1, e.retry_after_s
        fe.service.resume()
        fe.collect()
        st = fe.stats()
        lat = {k: f"p99={v['p99_ms']:.2f}ms" for k, v in
               st["classes"].items() if v["samples"]}
        print(f"front door: {lat}; admitted="
              f"{ {k: v['admitted'] for k, v in st['classes'].items()} }, "
              f"rejected typed Overloaded={rejected} "
              f"(retry in ~{hint * 1e3:.1f}ms), availability="
              f"{st['availability_admitted']:.3f}, tenants="
              f"{sorted(st['tenants'])}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
