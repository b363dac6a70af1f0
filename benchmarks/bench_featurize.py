"""Paper Table 6: the featurization catalog, one benchmark per row —
dictionary-domain cost (K) for each transform + the device gather path
through the Pallas kernels (interpret mode on CPU) + the serving path:
seed-style synchronous FeaturePipeline.batch() loop vs the pump-driven
FeatureService (the ≥1.5x throughput gate) vs the packed fast path
(device-resident word streams: scan ranges AND uniform arbitrary-row
requests, both served by coalesced index-only launches)."""
from __future__ import annotations

import os
from collections import deque

import numpy as np
import jax
import jax.numpy as jnp

from repro.columnar import Dictionary, Table
from repro.core import (AugmentedDictionary, FeatureExecutor,
                        FeaturePipeline, FeaturePlan, FeatureSet,
                        ShardedFeatureExecutor)
from repro.core.pipeline import pad_rows_edge
from repro.kernels.adv_gather import adv_gather
from repro.kernels.hist import hist
from repro.serve import (FaultInjector, FaultPolicy, FeatureFrontend,
                         FeatureService, Overloaded, RequestClass)
from benchmarks.common import (MIN_REPEATS, time_call, emit, scaled,
                               interleaved_best)

K = 999


def _serve_comparison() -> None:
    """Seed loop (per-column dict transfer, sync retire per batch) vs
    FeatureService (stacked single transfer, background pump) vs the packed
    paths (device-resident words; scan ranges and random rows).

    All five loops are timed with ROUND-ROBIN best-of-N
    (``interleaved_best``): the CI gate compares ratios between them, and
    interleaving keeps machine-speed drift from landing on one contender.
    """
    rng = np.random.default_rng(11)
    n = scaled(200_000, 8_000)
    batch = scaled(512, 128)
    n_batches = scaled(200, 50)    # smoke needs enough batches for a stable
                                   # CI perf gate; loops timed best-of-N
    table = Table.from_data({
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
        "device": rng.integers(0, 4, n),
    })
    fs = (FeatureSet().add("age", "zscore")
          .add("age", "bucketize", boundaries=(30.0, 45.0, 65.0))
          .add("state", "onehot")
          .add("income", "minmax").add("income", "log")
          .add("device", "onehot"))
    pipe = FeaturePipeline(table, fs)
    plan = pipe.plan
    idx_list = [rng.integers(0, n, batch) for _ in range(n_batches)]
    rows = batch * n_batches

    # 1. seed FeaturePipeline.batch() semantics: one transfer per column
    # (dict input), synchronous host retire of every batch
    cols = plan.columns
    codes_host = {c: plan.codes_matrix[i] for i, c in enumerate(cols)}
    tables = {c: plan.plans[i].fused_table for i, c in enumerate(cols)}

    @jax.jit
    def gather_dict(code_batch):
        outs = [jnp.take(tables[c], code_batch[c], axis=0) for c in cols]
        return jnp.concatenate(outs, axis=-1)

    def seed_loop():
        for ix in idx_list:
            np.asarray(gather_dict({c: jnp.asarray(codes_host[c][ix])
                                    for c in cols}))

    # 2. pump-driven service over the int32 plan
    svc = FeatureService(plan, prefetch=2, buckets=(batch,))

    def svc_loop():
        for ix in idx_list:
            svc.submit(ix)
        svc.drain()

    # 3. packed scan pattern: word-aligned ranges (the training-epoch serve
    # pattern) — the pump coalesces them into index-only launches
    plan_packed = FeaturePlan(table, fs, packed=True)
    svcp = FeatureService(plan_packed, prefetch=2, buckets=(batch,))
    start_list = [int(s) * batch
                  for s in rng.integers(0, n // batch, n_batches)]

    def packed_loop():
        for st in start_list:
            svcp.submit(np.arange(st, st + batch))
        svcp.drain()

    # 4/5. uniform arbitrary-row requests, mixed sizes — the realistic
    # 'millions of users' lookup pattern — served two ways over the SAME
    # packed plan: the pre-PR host-gather path (host word-gather + (C, B)
    # code shipping + one un-coalesced launch per request, prefetch-2
    # retire) vs the pump's coalesced indexed launches (the device computes
    # word index + bit offset itself; only 4B x rows of indices move)
    sizes = [int(s) for s in
             rng.choice([batch // 4, batch // 2, batch], n_batches)]
    req_list = [rng.integers(0, n, sz) for sz in sizes]
    rand_rows = int(np.sum(sizes))
    ex = FeatureExecutor(plan_packed, prefetch=2)

    def host_gather_loop():
        inflight = deque()
        for req in req_list:
            padded = pad_rows_edge(req, batch)
            codes = plan_packed.host_codes(padded)        # host materializes
            inflight.append(ex.gather_device(jax.device_put(codes)))
            if len(inflight) >= 2:
                np.asarray(inflight.popleft())
        while inflight:
            np.asarray(inflight.popleft())

    svcr = FeatureService(plan_packed, prefetch=2, buckets=(batch,))

    def random_loop():
        for req in req_list:
            svcr.submit(req)
        svcr.drain()

    loops = [seed_loop, svc_loop, packed_loop, host_gather_loop, random_loop]
    for loop in loops:
        loop()                                             # compile each
    h2d_before = svcr.stats["bytes_h2d"]
    launches_before = svcr.stats["launches"]
    # 10 interleaved repeats (not the 5-minimum): the pump-driven loops are
    # the most sensitive to transient box load (thread handoffs balloon
    # under contention), and extra rounds raise the odds every contender's
    # min comes from a comparably quiet window
    repeats = 2 * MIN_REPEATS
    seed_s, svc_s, packed_s, host_s, random_s = \
        interleaved_best(loops, repeats=repeats)
    assert svcp.stats["packed_ranges"] >= n_batches        # fast path taken
    # per-loop averages over the interleaved repeats (stats accumulate)
    launches = (svcr.stats["launches"] - launches_before) / repeats
    h2d = (svcr.stats["bytes_h2d"] - h2d_before) / repeats

    emit("serve/seed_batch_loop", seed_s / n_batches * 1e6,
         f"rows_per_s={rows/seed_s:.0f}")
    emit("serve/feature_service_prefetch2", svc_s / n_batches * 1e6,
         f"rows_per_s={rows/svc_s:.0f};speedup={seed_s/svc_s:.2f}x")
    emit("serve/feature_service_packed", packed_s / n_batches * 1e6,
         f"rows_per_s={rows/packed_s:.0f};"
         f"speedup_vs_prefetch2={svc_s/packed_s:.2f}x;"
         f"h2d_bytes_int32={plan.bytes_moved_adv(batch)};"
         f"h2d_bytes_packed={plan_packed.bytes_moved_adv(batch)};"
         f"bytes_reduction="
         f"{plan.bytes_moved_adv(batch)/plan_packed.bytes_moved_adv(batch):.1f}x")
    emit("serve/feature_service_random_hostgather", host_s / n_batches * 1e6,
         f"rows_per_s={rand_rows/host_s:.0f};"
         f"code_bytes_per_req={4 * len(plan_packed.plans) * batch}")
    emit("serve/feature_service_random", random_s / n_batches * 1e6,
         f"rows_per_s={rand_rows/random_s:.0f};"
         f"speedup_vs_hostgather={host_s/random_s:.2f}x;"
         f"launches_per_loop={launches:.0f};"
         f"index_bytes_per_loop={h2d:.0f}")
    for s in (svc, svcp, svcr):        # pump threads don't outlive the module
        s.shutdown()


def _sharded_serve_comparison() -> None:
    """Mesh-sharded packed serving vs the pre-mesh single-stream path.

    Workload: clustered 'user block' lookups (64 contiguous rows at random
    word-aligned offsets — the per-user serving pattern), over a table
    partitioned into 4 IMCUs. Three contenders, interleaved best-of-N:

    - ``serve/feature_service_sharded_1shard`` — the 1-shard baseline: the
      SAME load served without per-IMCU device residency, i.e. the pre-mesh
      deployment path where the data moves to the compute — host word-gather
      + per-request (C, B) code shipping + one un-coalesced launch stream
      (prefetch-2 retire). This is the ``feature_service_random_hostgather``
      methodology from the PR 3 gate, applied to the mesh workload.
    - ``serve/feature_service_sharded`` — the mesh service: per-IMCU
      resident word-stream shards committed to the mesh devices
      (XLA_FLAGS=--xla_force_host_platform_device_count=4 in CI), rows
      routed to their owning shard at submit, per-shard coalescing
      (coalesce=8) with a 1ms linger, per-shard prefetch windows, one
      multiplexing pump. Compute moves to the data; only 4B x rows of
      indices ever cross host->device.
    - the same-code RESIDENT 1-shard service, reported in the sharded
      record's derived field (``resident1_parity``): on a small-core CPU
      host same-code shard scaling is core-bound, so parity (~1x) is the
      ceiling — the mesh's win there is capacity (one device's memory
      cannot hold every stream at scale) while THIS record's gated claim is
      against the path a mesh deployment would otherwise serve through.
    """
    rng = np.random.default_rng(17)
    n = scaled(256_000, 64_000)
    n_req = scaled(600, 300)
    rsz = 64
    n_shards = 4
    data = {
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
        "device": rng.integers(0, 4, n),
    }
    fs = (FeatureSet().add("age", "zscore")
          .add("age", "bucketize", boundaries=(30.0, 45.0, 65.0))
          .add("state", "onehot")
          .add("income", "minmax").add("income", "log")
          .add("device", "onehot"))
    plan_mesh = FeaturePlan(Table.from_data(data, imcu_rows=n // n_shards),
                            fs, packed=True)
    plan_one = FeaturePlan(Table.from_data(data), fs, packed=True)
    plan_res1 = FeaturePlan(Table.from_data(data), fs, packed=True)
    ex_one = FeatureExecutor(plan_one, prefetch=2)
    starts = rng.integers(0, (n - rsz) // 32, n_req) * 32
    reqs = [np.arange(s, s + rsz) for s in starts]
    rows = n_req * rsz

    def baseline_loop():
        # pre-mesh path: the host gathers packed words per request and
        # ships int32 code slices to the one compute device, one launch
        # per request, prefetch-2 retire — data moves to the compute
        inflight = deque()
        for r in reqs:
            codes = plan_one.host_codes(r)
            inflight.append(ex_one.gather_device(jax.device_put(codes)))
            if len(inflight) >= 2:
                np.asarray(inflight.popleft())
        while inflight:
            np.asarray(inflight.popleft())

    svc = FeatureService(plan_mesh, sharded=True, buckets=(rsz,),
                         coalesce=8, linger_us=1000)
    svc1 = FeatureService(plan_res1, sharded=True, buckets=(rsz,),
                          coalesce=8, linger_us=1000)

    def mesh_loop():
        for r in reqs:
            svc.submit(r)
        svc.drain()

    def resident1_loop():
        for r in reqs:
            svc1.submit(r)
        svc1.drain()

    loops = [baseline_loop, mesh_loop, resident1_loop]
    for loop in loops:
        loop()                                             # compile each
    launches_before = svc.stats["launches"]
    repeats = 2 * MIN_REPEATS
    base_s, mesh_s, res1_s = interleaved_best(loops, repeats=repeats)
    launches = (svc.stats["launches"] - launches_before) / repeats
    emit("serve/feature_service_sharded_1shard", base_s / n_req * 1e6,
         f"rows_per_s={rows/base_s:.0f};"
         f"path=host_word_gather+code_ship,1_launch_stream;"
         f"code_bytes_per_req={4 * len(plan_one.plans) * rsz}")
    emit("serve/feature_service_sharded", mesh_s / n_req * 1e6,
         f"rows_per_s={rows/mesh_s:.0f};"
         f"speedup_vs_1shard={base_s/mesh_s:.2f}x;"
         f"shards={svc.n_shards};devices={len(jax.devices())};"
         f"launches_per_loop={launches:.0f};"
         f"resident1_parity={res1_s/mesh_s:.2f}x;"
         f"shard_launches={svc.stats['shard_launches']}")
    for s in (svc, svc1):
        s.shutdown()


def _skewed_serve_comparison() -> None:
    """Adaptive hot-shard replication under Zipf-distributed hot keys.

    Workload: clustered 64-row 'user block' lookups whose block index is
    Zipf-distributed — the head of the distribution (the hot users) lives
    in shard 0's row range, so single-owner routing concentrates most
    traffic on ONE shard's launch stream while the other devices idle.
    Three contenders, interleaved best-of-N, per the PR 3/4 gate
    methodology (normalized same-run, machine speed cancels):

    - ``serve/feature_service_skewed_1owner`` — the single-owner-routing
      baseline: the SAME skewed load served without adaptive shard
      management, i.e. the pre-adaptive deployment path where every row
      has exactly one serving stream — host word-gather + per-request
      (C, B) code shipping + one un-coalesced launch stream (prefetch-2
      retire). The ``feature_service_sharded_1shard`` methodology, under
      skew.
    - ``serve/feature_service_skewed`` — the adaptive mesh service: the
      load monitor's request-rate EWMA flags shard 0 as hot during
      warm-up, ``rebalance()`` replicates its resident word stream across
      the under-loaded devices (read fan-out), and the steady state is
      timed. Each replica stream brings its own prefetch window + device
      queue: on a real mesh that multiplies the hot shard's HBM/compute
      capacity; on a shared-memory CPU host the fan-out win is pipeline
      depth only, so the same-code no-replication service is ALSO timed
      and reported as ``owner_routing_parity`` in the derived field (the
      ``resident1_parity`` transparency convention from PR 4).
    """
    rng = np.random.default_rng(29)
    n = scaled(256_000, 64_000)
    n_req = scaled(800, 400)
    rsz = 64
    n_shards = 4
    data = {
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
        "device": rng.integers(0, 4, n),
    }
    fs = (FeatureSet().add("age", "zscore")
          .add("age", "bucketize", boundaries=(30.0, 45.0, 65.0))
          .add("state", "onehot")
          .add("income", "minmax").add("income", "log")
          .add("device", "onehot"))
    # Zipf-distributed hot keys: block rank r served with p ~ 1/r^1.2, the
    # head mapped to the lowest rows — hot users cluster in shard 0
    blocks = (n - rsz) // 32
    ranks = np.minimum(rng.zipf(1.2, n_req), blocks) - 1
    reqs = [np.arange(s, s + rsz) for s in ranks * 32]
    hot_share = float(np.mean(ranks * 32 < n // n_shards))
    rows = n_req * rsz

    table_mesh = Table.from_data(data, imcu_rows=n // n_shards)
    plan_one = FeaturePlan(Table.from_data(data), fs, packed=True)
    ex_one = FeatureExecutor(plan_one, prefetch=2)

    def owner_loop():
        # single-owner routing, pre-adaptive path: every request is served
        # by its one owning stream — host word-gather + code ship + one
        # launch stream, prefetch-2 retire (data moves to the compute)
        inflight = deque()
        for r in reqs:
            codes = plan_one.host_codes(r)
            inflight.append(ex_one.gather_device(jax.device_put(codes)))
            if len(inflight) >= 2:
                np.asarray(inflight.popleft())
        while inflight:
            np.asarray(inflight.popleft())

    svc = FeatureService(FeaturePlan(table_mesh, fs, packed=True),
                         sharded=True, buckets=(rsz,), coalesce=8,
                         linger_us=1000, hot_factor=2.0, max_replicas=3)
    svc_par = FeatureService(FeaturePlan(table_mesh, fs, packed=True),
                             sharded=True, buckets=(rsz,), coalesce=8,
                             linger_us=1000)

    def adaptive_loop():
        for r in reqs:
            svc.submit(r)
        svc.drain()

    def parity_loop():
        for r in reqs:
            svc_par.submit(r)
        svc_par.drain()

    loops = [owner_loop, adaptive_loop, parity_loop]
    for loop in loops:
        loop()                                             # compile each
    for _ in range(3):          # monitor converges on the skew in warm-up
        adaptive_loop()
        svc.rebalance()
    replicas = svc.replicas
    assert replicas[0] >= 1, "monitor failed to replicate the hot shard"
    repeats = 2 * MIN_REPEATS
    owner_s, adapt_s, par_s = interleaved_best(loops, repeats=repeats)
    emit("serve/feature_service_skewed_1owner", owner_s / n_req * 1e6,
         f"rows_per_s={rows/owner_s:.0f};"
         f"path=single_owner,host_word_gather+code_ship,1_launch_stream;"
         f"hot_share={hot_share:.2f}")
    emit("serve/feature_service_skewed", adapt_s / n_req * 1e6,
         f"rows_per_s={rows/adapt_s:.0f};"
         f"speedup_vs_1owner={owner_s/adapt_s:.2f}x;"
         f"owner_routing_parity={par_s/adapt_s:.2f}x;"
         f"replicas={replicas};hot_share={hot_share:.2f};"
         f"devices={len(jax.devices())};"
         f"shard_launches={svc.stats['shard_launches']}")
    for s in (svc, svc_par):
        s.shutdown()


def _chaos_serve_comparison() -> None:
    """Availability + tail latency under periodic injected replica faults.

    The same Zipf 'user block' workload as ``feature_service_skewed``,
    served by two same-run services: a fault-free reference and one whose
    hot shard (0) keeps taking periodic launch faults on its primary AND
    its first replica (deterministic FaultInjector rules — every 4th/5th
    launch of those streams fails, forever). With a third healthy stream
    resident, failover retries keep every ticket completing: the
    ``compare.py --require`` gate asserts ``availability=1`` on this
    record, and ``p99_vs_clean`` reports the recovery machinery's tail
    cost against the fault-free same-run baseline (machine speed cancels;
    there is no cross-run gate on the ratio because injected-fault timing
    is scheduler-sensitive on shared CI hosts).
    """
    rng = np.random.default_rng(43)
    n = scaled(128_000, 32_000)
    n_req = scaled(400, 200)
    rsz = 64
    n_shards = 4
    data = {
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
    }
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("income", "minmax"))
    blocks = (n - rsz) // 32
    ranks = np.minimum(rng.zipf(1.2, n_req), blocks) - 1
    reqs = [np.arange(s, s + rsz) for s in ranks * 32]
    rows = n_req * rsz
    table = Table.from_data(data, imcu_rows=n // n_shards)

    inj = (FaultInjector()
           .fail_launches(1 << 30, shard=0, stream=0, every=4)
           .fail_launches(1 << 30, shard=0, stream=1, every=5))
    # breakers off (threshold unreachably high): the benchmark measures
    # the retry/failover path itself under a PERSISTENT fault source, not
    # the breaker's learned avoidance of it
    pol = FaultPolicy(max_retries=3, backoff_s=0.0005, breaker_fails=1 << 30)

    def build(faults, policy):
        svc = FeatureService(FeaturePlan(table, fs, packed=True),
                             sharded=True, buckets=(rsz,), coalesce=8,
                             linger_us=1000, max_replicas=3, faults=faults,
                             fault_policy=policy)
        svc.add_replica(0)          # 3 streams: 2 faulty + 1 healthy under
        svc.add_replica(0)          # the injector rules above
        return svc

    svc_clean = build(None, None)
    svc_chaos = build(inj, pol)

    def clean_loop():
        for r in reqs:
            svc_clean.submit(r)
        svc_clean.drain()

    def chaos_loop():
        for r in reqs:
            svc_chaos.submit(r)
        svc_chaos.drain()

    loops = [clean_loop, chaos_loop]
    for loop in loops:
        loop()                                             # compile each
    svc_clean.reset_latency_window()
    svc_chaos.reset_latency_window()
    failovers0 = svc_chaos.stats["failovers"]
    clean_s, chaos_s = interleaved_best(loops, repeats=MIN_REPEATS)
    p99_clean = svc_clean.latency_percentile(99)
    p99_chaos = svc_chaos.latency_percentile(99)
    st = svc_chaos.throughput_stats(chaos_s)
    emit("serve/feature_service_chaos_clean", clean_s / n_req * 1e6,
         f"rows_per_s={rows/clean_s:.0f};p99_ms={p99_clean*1e3:.3f};"
         f"replicas={svc_clean.replicas[0]}")
    emit("serve/feature_service_chaos", chaos_s / n_req * 1e6,
         f"availability={st['availability']:.4f};"
         f"failed_tickets={st['failed_tickets']};"
         f"failovers={st['failovers'] - failovers0};"
         f"retries={st['retries']};"
         f"faults_injected={inj.faults_injected};"
         f"p99_ms={p99_chaos*1e3:.3f};"
         f"p99_vs_clean={p99_chaos/max(p99_clean, 1e-9):.2f}x;"
         f"slowdown_vs_clean={chaos_s/clean_s:.2f}x;"
         f"replicas={svc_chaos.replicas[0]};"
         f"devices={len(jax.devices())}")
    for s in (svc_clean, svc_chaos):
        s.shutdown()


def _hedged_serve_comparison() -> None:
    """Tail latency under injected stragglers: hedged vs no-hedge, same run.

    The Zipf 'user block' workload again, with ASYNC stragglers (stall
    rules: every 4th launch on shard 0's primary stream holds its result
    buffer for ``stall_s`` — the pump keeps running, only the retire
    waits) and a replica resident on the hot shard. Two services differ in
    ONE policy bit: ``hedge``. The no-hedge control rides every stall out,
    so its p99 ~= the stall; the hedged service duplicates the launch on
    the replica once the wait crosses the hedge cutoff and retires the
    fast copy. The ``compare.py --require`` gate asserts availability=1
    AND hedge_wins>=1 AND ``p99_vs_nohedge`` well under 1 on this record —
    the speculative duplicate must actually beat the straggler, same-run
    so machine speed cancels (no cross-run timing gate: stall timing is
    scheduler-sensitive on shared CI hosts).
    """
    rng = np.random.default_rng(47)
    n = scaled(128_000, 32_000)
    n_req = scaled(400, 200)
    rsz = 64
    n_shards = 4
    stall_s = 0.05
    data = {
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
    }
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("income", "minmax"))
    blocks = (n - rsz) // 32
    ranks = np.minimum(rng.zipf(1.2, n_req), blocks) - 1
    reqs = [np.arange(s, s + rsz) for s in ranks * 32]
    rows = n_req * rsz
    table = Table.from_data(data, imcu_rows=n // n_shards)

    def build(hedge: bool):
        # each service needs its OWN injector: stall rules consume per
        # launch, and the two pumps interleave nondeterministically
        inj = FaultInjector().stall_launches(stall_s, 1 << 30, shard=0,
                                             stream=0, every=4)
        # breakers + straggler strikes off (thresholds unreachable): the
        # benchmark isolates the hedging machinery from learned avoidance
        pol = FaultPolicy(breaker_fails=1 << 30, straggler_min_s=1e9,
                          hedge=hedge, hedge_min_s=0.005, hedge_factor=4.0)
        svc = FeatureService(FeaturePlan(table, fs, packed=True),
                             sharded=True, buckets=(rsz,), coalesce=8,
                             linger_us=1000, max_replicas=3, faults=inj,
                             fault_policy=pol)
        svc.add_replica(0)           # the healthy stream hedges land on
        return svc, inj

    svc_hedge, inj_h = build(True)
    svc_plain, inj_p = build(False)

    def hedge_loop():
        for r in reqs:
            svc_hedge.submit(r)
        svc_hedge.drain()

    def plain_loop():
        for r in reqs:
            svc_plain.submit(r)
        svc_plain.drain()

    loops = [plain_loop, hedge_loop]
    for loop in loops:
        loop()                       # compile + train the EWMA past warmup
    svc_hedge.reset_latency_window()
    svc_plain.reset_latency_window()
    plain_s, hedge_s = interleaved_best(loops, repeats=MIN_REPEATS)
    p99_plain = svc_plain.latency_percentile(99)
    p99_hedge = svc_hedge.latency_percentile(99)
    st = svc_hedge.throughput_stats(hedge_s)
    emit("serve/feature_service_hedged_nohedge", plain_s / n_req * 1e6,
         f"rows_per_s={rows/plain_s:.0f};p99_ms={p99_plain*1e3:.3f};"
         f"stalls_injected={inj_p.stalls_injected};stall_ms={stall_s*1e3:.0f};"
         f"availability={svc_plain.throughput_stats(plain_s)['availability']:.4f}")
    emit("serve/feature_service_hedged", hedge_s / n_req * 1e6,
         f"availability={st['availability']:.4f};"
         f"failed_tickets={st['failed_tickets']};"
         f"hedges={st['hedges']};hedge_wins={st['hedge_wins']};"
         f"stalls_injected={inj_h.stalls_injected};"
         f"p99_ms={p99_hedge*1e3:.3f};"
         f"p99_vs_nohedge={p99_hedge/max(p99_plain, 1e-9):.3f}x;"
         f"speedup_vs_nohedge={plain_s/hedge_s:.2f}x;"
         f"replicas={svc_hedge.replicas[0]};"
         f"devices={len(jax.devices())}")
    for s in (svc_hedge, svc_plain):
        s.shutdown()


def _tiered_serve_comparison() -> None:
    """Tiered residency under memory pressure: a table ~10x the per-device
    HBM byte budget, Zipf(1.2) access, vs a same-run all-hot control.

    The table is cut into 16 IMCU shards but the byte budget only lets a
    few streams be device-resident at once; the Zipf head is mapped to the
    END of the table, so the hot blocks land on shards that START off
    budget (host-warm). During warm-up the monitor promotes the hot
    shards up (displacing the idle early residents down to warm/cold) and
    the steady state is timed: hot-tier launches for the head, parallel
    host-gather misses for the tail, no request ever blocking on a tier
    change. The all-hot control serves the SAME load with no budget
    (every stream resident) — the capacity a real mesh cannot afford at
    this table:budget ratio. The ``compare.py --require`` gate asserts
    ``table_x_budget>=8``, ``tiered_vs_hot>=0.5`` (throughput within 2x
    of all-hot while holding 1/10th of the bytes), ``availability=1``,
    ``bitexact=1``, and at least one observed promotion AND demotion.

    A second, untimed phase measures the miss window itself: two all-warm
    services (budget=1, so EVERY request is a host-gather miss) differing
    only in ``host_gather_workers`` (4 vs 1); the fan-out's p99 cut is
    reported as ``miss_p99_cut`` (not gated: the cut needs spare physical
    cores — on a 1-core CI host the pool can only lose, which is why the
    service's worker default is ``min(4, cpu_count)`` — and thread timing
    is scheduler-sensitive on shared hosts anyway; the record carries
    ``cpus`` so readers can interpret a cut below 1).
    """
    rng = np.random.default_rng(53)
    n = scaled(256_000, 64_000)
    n_req = scaled(600, 300)
    rsz = 64
    n_shards = 16
    data = {
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
    }
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("income", "minmax"))
    table = Table.from_data(data, imcu_rows=n // n_shards)

    # size the budget off the real stream bytes: a probe executor with a
    # 1-byte budget commits nothing but still projects every stream
    probe = ShardedFeatureExecutor(
        FeaturePlan(Table.from_data(data, imcu_rows=n // n_shards), fs,
                    packed=True), hbm_budget_bytes=1)
    total_bytes = sum(e.stream_nbytes() for e in probe.executors)
    budget = max(1, total_bytes // 10)
    table_x_budget = total_bytes / budget

    # Zipf(1.2) block ranks mapped to the table END: the hot head lives in
    # the LAST shards — exactly the ones the in-order budget commit left
    # host-warm, so serving pressure must promote them up the ladder
    blocks = (n - rsz) // 32
    ranks = np.minimum(rng.zipf(1.2, n_req), blocks) - 1
    starts = (blocks - 1 - ranks) * 32
    reqs = [np.arange(s, s + rsz) for s in starts]
    rows = n_req * rsz

    plan_t = FeaturePlan(table, fs, packed=True)
    svc = FeatureService(plan_t, sharded=True, buckets=(rsz,), coalesce=8,
                         linger_us=1000, rebalance_every=8, max_replicas=0,
                         hbm_budget_bytes=budget, cold_after=4,
                         host_gather_workers=4)
    svc_hot = FeatureService(
        FeaturePlan(Table.from_data(data, imcu_rows=n // n_shards), fs,
                    packed=True),
        sharded=True, buckets=(rsz,), coalesce=8, linger_us=1000,
        max_replicas=0)

    def tiered_loop():
        for r in reqs:
            svc.submit(r)
        svc.drain()

    def hot_loop():
        for r in reqs:
            svc_hot.submit(r)
        svc_hot.drain()

    loops = [hot_loop, tiered_loop]
    for loop in loops:
        loop()                     # compile
    for _ in range(3):             # monitor converges: head promotes up
        tiered_loop()
    assert svc.stats["promotions"] >= 1, \
        f"monitor never promoted: tiers={svc.tiers} stats={svc.stats}"
    # bit-exact spot check across all tiers (untimed): service output vs
    # the parent plan's host featurize path
    checks = [reqs[0], reqs[-1], np.arange(0, rsz),          # cold/warm head
              rng.integers(0, n, 200)]                       # scatter
    bitexact = all(
        np.array_equal(svc.result(svc.submit(r)), plan_t.host_features(r))
        for r in checks)
    hot_s, tier_s = interleaved_best(loops, repeats=2 * MIN_REPEATS)
    st = svc.throughput_stats(tier_s)
    tiers = svc.tiers
    emit("serve/feature_service_tiered_allhot", hot_s / n_req * 1e6,
         f"rows_per_s={rows/hot_s:.0f};shards={svc_hot.n_shards};"
         f"devices={len(jax.devices())}")
    emit("serve/feature_service_tiered", tier_s / n_req * 1e6,
         f"rows_per_s={rows/tier_s:.0f};"
         f"tiered_vs_hot={hot_s/tier_s:.2f}x;"
         f"table_x_budget={table_x_budget:.1f}x;"
         f"availability={st['availability']:.4f};"
         f"bitexact={int(bitexact)};"
         f"promotions={svc.stats['promotions']};"
         f"demotions={svc.stats['demotions']};"
         f"rehydrations={svc.stats['rehydrations']};"
         f"tier_misses={svc.stats['tier_misses']};"
         f"tier_hot={tiers.count('hot')};tier_warm={tiers.count('warm')};"
         f"tier_cold={tiers.count('cold')};"
         f"budget_bytes={budget};stream_bytes={total_bytes};"
         f"devices={len(jax.devices())}")

    # miss-window phase: all-warm (budget=1) services, pool fan-out 4 vs 1
    def build_miss(workers: int) -> FeatureService:
        return FeatureService(
            FeaturePlan(Table.from_data(data, imcu_rows=n // n_shards), fs,
                        packed=True),
            sharded=True, buckets=(rsz,), coalesce=8, linger_us=1000,
            max_replicas=0, hbm_budget_bytes=1, host_gather_workers=workers)

    svc_m4, svc_m1 = build_miss(4), build_miss(1)
    p99 = {}
    for workers, sm in ((4, svc_m4), (1, svc_m1)):
        for r in reqs[:50]:
            sm.submit(r)
        sm.drain()                 # warm the pool + caches
        sm.reset_latency_window()
        for r in reqs:
            sm.submit(r)
        sm.drain()
        p99[workers] = sm.latency_percentile(99)
        assert sm.stats["promotions"] == 0     # nothing ever fits
    emit("serve/feature_service_tiered_miss_p99",
         p99[4] * 1e6,
         f"miss_p99_ms={p99[4]*1e3:.3f};"
         f"miss_p99_1thread_ms={p99[1]*1e3:.3f};"
         f"miss_p99_cut={p99[1]/max(p99[4], 1e-9):.2f}x;"
         f"host_gather_workers=4;cpus={os.cpu_count()};"
         f"misses={svc_m4.stats['tier_misses']}")
    for s in (svc, svc_hot, svc_m4, svc_m1):
        s.shutdown()


def _frontend_serve_comparison() -> None:
    """Per-class SLOs through the multi-tenant front door under saturation.

    The Zipf 'user block' workload split across request classes and
    pushed through :class:`FeatureFrontend` as a saturating burst (every
    submit lands before the pump can drain, so queues build and the
    scheduler's choices decide who waits): ``interactive`` (priority 3,
    singleton groups, no linger) interleaved 1:3 into a ``batch`` stream
    (priority 2, coalesce 8, 1 ms linger) plus a trickle of ``background``
    scavenger work. The per-class p99s come from the service's streaming
    latency histograms (reset after the compile warmup, so they cover
    only steady-state tickets); the CI ``--require`` gates assert the
    SLO ordering ``p99_interactive_vs_batch < 1`` (priority scheduling
    actually protects the interactive tail — a same-run ratio, machine
    speed cancels), ``availability=1`` over every ADMITTED ticket,
    ``background_completed >= 1`` (anti-starvation aging drains the
    scavenger class under pressure) and ``overloaded >= 1`` (the
    admission probe below really exercised typed rejection). The FIFO
    control record serves the identical mixed burst classless through the
    same-shaped service — the one-queue world whose tail every class
    shares.
    """
    rng = np.random.default_rng(47)
    n = scaled(128_000, 32_000)
    n_inter = scaled(120, 60)
    n_batch = scaled(360, 180)
    n_bg = 8
    rsz = 64
    n_shards = 4
    data = {
        "age": rng.integers(18, 90, n),
        "state": rng.integers(0, 50, n),
        "income": rng.integers(20, 250, n) * 1000,
    }
    fs = (FeatureSet().add("age", "zscore").add("state", "onehot")
          .add("income", "minmax"))
    blocks = (n - rsz) // 32

    def zipf_reqs(count):
        ranks = np.minimum(rng.zipf(1.2, count), blocks) - 1
        return [np.arange(s, s + rsz) for s in ranks * 32]

    reqs_batch = zipf_reqs(n_batch)
    reqs_inter = zipf_reqs(n_inter)
    reqs_bg = zipf_reqs(n_bg)
    n_req = n_batch + n_inter + n_bg
    table = Table.from_data(data, imcu_rows=n // n_shards)

    classes = (
        RequestClass("interactive", priority=3, coalesce=1, linger_us=0.0,
                     max_inflight=512, queue_depth=512),
        RequestClass("batch", priority=2, coalesce=8, linger_us=1000.0,
                     max_inflight=1024, queue_depth=1024),
        # tiny admission window: the post-timing probe overflows it to
        # prove typed Overloaded rejection (the timed trickle fits)
        RequestClass("background", priority=1, aging_s=0.05,
                     max_inflight=16, queue_depth=16),
    )

    def build(klasses):
        return FeatureService(FeaturePlan(table, fs, packed=True),
                              sharded=True, buckets=(rsz,), coalesce=8,
                              linger_us=1000, classes=klasses)

    svc = build(classes)
    fe = FeatureFrontend(svc)
    svc_fifo = build(None)

    bg_step = n_batch // n_bg

    def fe_loop():
        k = 0
        for i, r in enumerate(reqs_batch):
            fe.submit(r, klass="batch", tenant="analytics")
            if i % 3 == 0 and k < n_inter:
                fe.submit(reqs_inter[k], klass="interactive",
                          tenant="app")
                k += 1
            if i % bg_step == 0 and i // bg_step < n_bg:
                fe.submit(reqs_bg[i // bg_step],
                          klass="background", tenant="scavenger")
        while k < n_inter:
            fe.submit(reqs_inter[k], klass="interactive", tenant="app")
            k += 1
        fe.collect()

    def fifo_loop():
        for i, r in enumerate(reqs_batch):
            svc_fifo.submit(r)
            if i % 3 == 0:
                svc_fifo.submit(reqs_inter[i // 3 % n_inter])
        svc_fifo.drain()

    loops = [fifo_loop, fe_loop]
    for loop in loops:
        loop()                                             # compile each
    svc.reset_latency_window()
    svc_fifo.reset_latency_window()
    fifo_s, fe_s = interleaved_best(loops, repeats=MIN_REPEATS)

    inter_p99 = svc.latency_percentile(99, "interactive")
    batch_p99 = svc.latency_percentile(99, "batch")
    cs = svc.class_stats()
    # admission probe: overflow the background window while the pump is
    # held — every submit past window + depth must raise typed Overloaded
    svc.pause()
    overloaded, retry_hint = 0, 0.0
    for _ in range(64):
        try:
            fe.submit(reqs_bg[0], klass="background", tenant="scavenger")
        except Overloaded as e:
            overloaded += 1
            retry_hint = e.retry_after_s
    svc.resume()
    fe.collect()
    st = fe.stats()
    emit("serve/feature_service_frontend_fifo", fifo_s / n_req * 1e6,
         f"p99_ms={svc_fifo.latency_percentile(99)*1e3:.3f};"
         f"rows_per_s={(n_batch + n_inter)*rsz/fifo_s:.0f}")
    emit("serve/feature_service_frontend", fe_s / n_req * 1e6,
         f"interactive_p99_ms={inter_p99*1e3:.3f};"
         f"batch_p99_ms={batch_p99*1e3:.3f};"
         f"p99_interactive_vs_batch={inter_p99/max(batch_p99, 1e-9):.3f}x;"
         f"availability={st['availability_admitted']:.4f};"
         f"background_completed={cs['background']['completed']};"
         f"overloaded={overloaded};"
         f"retry_after_ms={retry_hint*1e3:.3f};"
         f"admitted={sum(c['admitted'] for c in st['classes'].values())};"
         f"latency_samples={svc.stats['latency_samples_total']};"
         f"devices={len(jax.devices())}")
    fe.shutdown()
    svc_fifo.shutdown()


def run() -> None:
    N = scaled(1 << 16, 1 << 12)   # device-path rows (interpret mode is slow)
    rng = np.random.default_rng(3)
    ages = rng.integers(0, K, N)
    d, codes = Dictionary.from_data(ages)
    aug = AugmentedDictionary(d)

    catalog = [
        ("float", {}), ("onehot", {"max_cardinality": 4096}),
        ("minmax", {}), ("mean_norm", {}), ("zscore", {}),
        ("binarize", {"threshold": 500.0}),
        ("quantile", {"q": 4}), ("hash_bucket", {"n_buckets": 32}),
        ("bucketize", {"boundaries": np.linspace(0, K, 7)[1:-1]}),
        ("embedding", {"dim": 16}),
    ]
    for kind, params in catalog:
        us = time_call(lambda k=kind, p=params:
                       AugmentedDictionary(d).add(f"b_{k}", k, **p),
                       repeats=5)
        emit(f"table6/build_{kind}", us, f"K={d.cardinality}")

    # row-space application = one gather regardless of transform
    aug.add("zscore", "zscore")
    us = time_call(aug.featurize, "zscore", codes, repeats=5)
    emit("table6/apply_gather_host", us, f"N={N}")

    # device path: Pallas adv_gather (interpret) + count-metadata hist build
    table = jnp.asarray(aug["zscore"].table)
    jcodes = jnp.asarray(codes)
    adv_gather(table, jcodes).block_until_ready()
    us = time_call(lambda: adv_gather(table, jcodes).block_until_ready(),
                   repeats=3)
    emit("table6/apply_gather_pallas_interp", us, f"N={N}")
    hist(jcodes, d.cardinality).block_until_ready()
    us = time_call(lambda: hist(jcodes, d.cardinality).block_until_ready(),
                   repeats=3)
    emit("table6/count_metadata_build_pallas", us, f"K={d.cardinality}")

    _serve_comparison()
    _sharded_serve_comparison()
    _skewed_serve_comparison()
    _chaos_serve_comparison()
    _hedged_serve_comparison()
    _tiered_serve_comparison()
    _frontend_serve_comparison()


if __name__ == "__main__":
    run()
