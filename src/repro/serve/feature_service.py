"""FeatureService: pump-driven, coalescing, mesh-shardable ADV serving.

The serving-side rendering of the paper's §6 pipeline: learned features are
served directly out of the data system ('codes in, features out'), not
exported and recomputed. A request names table rows; the service chunks it
to static bucket shapes (the same trick :class:`repro.serve.engine.ServeEngine`
uses for token batches, so jit compiles once per bucket) and queues the
chunks on the launch queue of the shard that owns their rows.

Serving architecture (request -> route -> per-shard coalescer -> one
multiplexing pump -> per-shard launch streams)::

    submit(rows) --route by IMCU--> [shard 0 queue] --group--\\   pump
                               \\--> [shard 1 queue] --group--->  (one
                                          ...                /   thread)
                 launch async on dev 0 / dev 1 / ... <-------/
              results <-- retire into per-ticket buffers (request order)

Unsharded services have exactly one queue (the PR 3 architecture,
unchanged); ``sharded=True`` over a packed plan builds one
:class:`repro.core.ShardedFeatureExecutor` — per-IMCU resident word-stream
shards, each committed to its own mesh device. A request's rows are
bucketed by owning IMCU on host at submit time (whole-request fast path
when one shard owns them all — the clustered 'user block' pattern); each
shard's queue coalesces up to ``coalesce`` same-bucket chunks into ONE
launch against its local shard, with ``prefetch`` launches in flight *per
shard*, so independent shards' gathers run concurrently on their own
devices instead of serializing through one launch stream. ONE pump thread
multiplexes every stream — launches dispatch asynchronously, so the
devices overlap while the pump runs ahead; a thread per shard would fight
the client for the GIL on exactly the small-core hosts that need the
overlap most (measured 0.3-0.6x; dispatch is the cheap part). Results are
reassembled in request order via per-chunk destination maps.

Packed serving ships indices only: every chunk — word-aligned range or
arbitrary row set — is served by the indexed gather
(:meth:`FeatureExecutor._rows_future`): the kernel computes word index +
bit offset against the resident streams, so the per-launch host->device
traffic is the padded (lanes x bucket) int32 index vector, where a
launch's lanes are its request class's coalesce depth.
``stats['bytes_h2d']`` therefore reports INDEX bytes; int32 plans still
ship (C, bucket) code slices and account those. Per-shard attribution
lives in ``stats['shard_launches'] / ['shard_batches'] /
['shard_bytes_h2d']`` (lists indexed by shard, summing to the totals).

``linger_us`` adds a latency-aware pump policy (bounded-latency
coalescing): under light load a pump may hold a PARTIAL launch group open
until the group's oldest chunk has been queued ``linger_us`` microseconds,
trading that bounded wait for fuller groups (backpressure already grows
groups under heavy load, so lingering only ever engages when the queue is
shallow). ``linger_us=0`` (default) launches whatever is queued per tick —
the PR 3 behavior.

``pause``/``resume`` hold launches (queueing continues) so callers can
force maximal coalescing; ``shutdown`` (also via the context-manager
protocol) drains the queues and joins every pump thread. Services hold
live threads — call :meth:`shutdown` (or use ``with``) when disposing of
one.

Adaptive shard management (mesh mode): the shard set is no longer frozen
at plan-build time. A load monitor fed by the per-shard stats deltas
(request-rate EWMA over ``stats['shard_batches']``) drives two policies —

- **hot-shard replication with read fan-out**: when one shard's EWMA runs
  ``hot_factor`` x the mean of the OTHER shards' (so the threshold stays
  reachable at any shard count), its resident word stream is replicated to
  the least-loaded device and the pump round-robins that shard's launches
  across the copies. Each replica stream brings its own ``prefetch``-deep
  in-flight window, so a hot shard's aggregate service capacity (launch
  windows x devices) scales with replicas; a ``refresh()`` write
  invalidates every copy for free because replicas re-sync from the
  parent plan's versioned words at their next launch. Cold shards shed
  replicas again (EWMA below the mean).
- **tail re-shard**: streaming appends extend only the open tail shard;
  past ``row_budget`` rows the tail is split at a word-aligned cut, the
  new shard's stream slice is committed to an under-loaded device, and
  the routing table (bisect bounds + per-shard queues + stats lanes) is
  swapped atomically — queued chunks of the old tail are re-routed (and
  split when they straddle the cut) under the service lock, so no
  in-flight ticket is dropped, reordered, or served from the wrong slice.

Both policies run ONLY on the pump thread (the sole launcher), either
automatically every ``rebalance_every`` launches or on demand via
:meth:`rebalance` / :meth:`add_replica` / :meth:`drop_replica` /
:meth:`split_tail`, which marshal onto the pump and block for the result —
so a shard-set mutation can never race a launch that is being dispatched.

Fault tolerance (launch-level isolation, replica failover, deadlines):
an exception during a launch or its retire fails ONLY the chunks of that
launch group — every other shard and queued request keeps serving, and
only errors in the pump's own control logic (outside the guarded launch/
retire paths) remain terminal. A failed group re-enqueues at the head of
its shard's queue with capped exponential backoff
(:class:`repro.serve.faults.FaultPolicy`); each chunk remembers the
streams it already failed on, so on a shard with replicas the retry
routes to a DIFFERENT copy immediately (no backoff — replica failover
turns replication into an availability mechanism). A stream that keeps
failing — thrown launches or straggler-flagged latencies (the per-shard
:class:`repro.train.fault.StragglerDetector` over launch round-trip
times) — opens its circuit breaker: the pump stops routing to it until a
cooldown passes, then the next round-robin launch is the recovery probe;
the monitor's third policy re-replicates shards whose streams are
unhealthy onto devices that are not. Retries exhausted, the affected
tickets resolve to a typed :class:`repro.serve.faults.ServeError`
surfaced per-ticket by :meth:`poll`/:meth:`result`/:meth:`collect` — the
service itself stays up and keeps accepting submits. ``deadline_ms`` on
:meth:`submit` evicts a request's still-queued chunks once expired (the
ticket resolves to :class:`repro.serve.faults.DeadlineExceeded`, also a
``TimeoutError``), and ``timeout=`` on :meth:`result`/:meth:`collect`/
:meth:`drain` bounds every blocking wait. The chaos harness
(:class:`repro.serve.faults.FaultInjector`, ``faults=`` — no-op by
default) injects deterministic failures and straggler delays ON the
launch path, so injected faults exercise exactly the recovery machinery
real device errors would.

Fault tolerance, phase 2 (component death, not just launch faults):

- **Device-loss recovery.** Launch failures are attributed to the
  failing stream's DEVICE (:class:`repro.serve.faults.DeviceHealth`):
  ``device_fails`` consecutive breaker trips — or one
  :class:`repro.serve.faults.DeviceDown` error — declare the device
  dead. The service then evicts every resident stream on it
  (:meth:`ShardedFeatureExecutor.evict_device` — replicas dropped,
  orphaned primaries promoted from surviving replicas) and shards left
  with NO live stream enter emergency rebuild: the monitor's fourth
  policy (and the pump, as soon as it has a free beat) re-commits the
  shard's word stream on a surviving device from the HOST packed words
  through the same version-keyed put path a refresh uses. Until the
  rebuild lands, the shard's queued chunks are served through the
  host-gather slow path (:meth:`FeaturePlan.host_features` — bit-exact
  with the device gather by construction), so availability holds at 1.0
  even with EVERY device dead.
- **Supervised pump restart.** The pump thread runs under a supervisor:
  a pump-infrastructure exception (control logic, not a guarded launch)
  no longer kills the service — the supervisor restarts the pump loop
  with the ledger intact (queues, in-flight windows, tickets, admin
  queue), re-enqueueing any group the dying pump had taken but not
  finished, up to ``FaultPolicy.pump_restarts`` times; past the budget
  the crash is terminal exactly like before. Blocking entry points
  (``result``/``drain``/``collect``/``poll``) already poll on 0.5 s
  ticks, so a restart is invisible to them.
- **Speculative hedged launches.** A retire wait that outlives
  ``max(hedge_min_s, hedge_factor x the shard's EWMA round-trip)`` (and
  a warmed-up detector) dispatches a DUPLICATE of the launch group on a
  different healthy stream of the same shard — the
  :class:`repro.train.fault.StragglerDetector` backup-worker idiom at
  serving granularity. First buffer to come ready resolves the tickets;
  the loser is discarded unread and never double-counts launch stats
  (only ``hedges``/``hedge_wins``). A hedge win also strikes the
  straggling primary's breaker, feeding the same unhealthy-stream
  machinery as a thrown launch.

Tiered residency (HBM-hot / host-warm / RLE-cold, ``hbm_budget_bytes``):
mesh services are no longer capped at tables that fit HBM. Every shard
carries a residency TIER — **hot** (device-resident packed words,
today's path), **warm** (host packed words only; served through the
host-gather path device loss already uses), **cold** (RLE runs from
:mod:`repro.columnar.rle`; the host packed copy is dropped too and
rehydrated on promotion) — and the load monitor moves shards up and
down the ladder under a per-device HBM byte budget
(:class:`repro.distributed.sharding.DeviceBudget`; live bytes are
always measured from the buffers actually held, never a drifting
ledger). Construction commits shards in order until the budget is
spent; the rest start warm. A request for an off-device shard is a
**tier miss**: it serves IMMEDIATELY through the host path — bit-exact
with the device gather by construction — and marks the shard
promotion-pending; promotion itself is ASYNCHRONOUS on the pump (a
free-beat action, like emergency rebuilds), re-committing the stream
through the same version-keyed put a refresh uses, displacing the
coldest resident shard first when the device is full (EWMA order,
strict — equal-heat shards never thrash). Warm shards quiet for
``cold_after`` consecutive monitor ticks compress to RLE runs. The
host-gather path itself fans a multi-chunk group out over a small
thread pool (``host_gather_workers``), cutting the miss-window p99.
Tier state: ``stats['tier_hot'/'tier_warm'/'tier_cold']`` (gauges),
``promotions``/``demotions``/``rehydrations``/``tier_misses``
(counters), :attr:`tiers`, :meth:`device_bytes`, and manual
:meth:`promote`/:meth:`demote` (admin actions on the pump, like every
shard-set mutation). Replication, device-loss rebuild and pushdown all
compose: policies skip off-device shards, a dead device's demoted
shards stay demoted (no rebuild — they were host-served anyway), and
promotion of a shard whose home device died rebuilds on a survivor.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field

import numpy as np
import jax.numpy as jnp

from repro.core.pipeline import (FeatureExecutor, FeaturePipeline,
                                 FeaturePlan, ShardedFeatureExecutor,
                                 pad_rows_edge)
from repro.serve.classes import LatencyHistogram, RequestClass
from repro.serve.faults import (DeadlineExceeded, DeviceDown, DeviceHealth,
                                FaultInjector, FaultPolicy, ServeError,
                                StreamBreaker)
from repro.spans import OFF, ids, span
from repro.train.fault import StragglerDetector

DEFAULT_BUCKETS = (64, 256, 1024)


@dataclass
class _Chunk:
    """One bucket-shaped slice of a request, queued for a shard's pump."""
    ticket: int
    rows: np.ndarray        # raw (unpadded) SHARD-LOCAL row indices
    n: int                  # valid rows (== rows.shape[0])
    bucket: int             # static launch shape this chunk pads to
    shard: int              # owning shard (0 for unsharded services)
    # destination of these rows in the request output: an int start for a
    # contiguous run, or an explicit position vector for routed splits
    dest: int | np.ndarray = 0
    t_enq: float = field(default=0.0, compare=False)
    # -- fault-recovery state (pump thread only) --
    attempts: int = 0               # launches tried so far
    not_before: float = 0.0         # retry backoff deadline (perf_counter)
    avoid: frozenset = frozenset()  # stream tokens this chunk failed on
    klass: str = "default"          # request class (pump scheduling key)


@dataclass
class _Flight:
    """One dispatched launch awaiting retire (pump thread only).

    ``ready_at`` gates the retire on an injected stall (simulated slow
    device compute — 0.0 means none). The hedge fields appear when a
    duplicate launch was dispatched on another stream: the duplicate
    covers the SAME group, so its buffer layout matches ``parts`` and
    whichever buffer comes ready first retires the tickets."""
    dev: object                     # primary launch buffer (device)
    parts: list                     # (ticket, n, dest, row_off) per chunk
    group: list                     # the _Chunks this launch covers
    ex: object                      # primary stream executor
    t0: float                       # primary dispatch time (perf_counter)
    ready_at: float = 0.0           # injected-stall retire gate
    hedge_dev: object = None        # duplicate launch buffer, if hedged
    hedge_ex: object = None
    hedge_t0: float = 0.0
    hedge_ready_at: float = 0.0
    hedge_done: bool = False        # hedge attempted (or impossible)


class FeatureService:
    """Request-queue-driven feature serving over a compiled FeaturePlan."""

    def __init__(self, plan: FeaturePlan | FeaturePipeline, *,
                 use_kernel: bool = False, prefetch: int = 2,
                 buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 sharded: bool = False, coalesce: int = 4,
                 linger_us: float = 0.0, devices=None,
                 rebalance_every: int = 0, row_budget: int | None = None,
                 hot_factor: float = 4.0, max_replicas: int | None = None,
                 hbm_budget_bytes: int | None = None, cold_after: int = 2,
                 host_gather_workers: int | None = None,
                 faults: FaultInjector | None = None,
                 fault_policy: FaultPolicy | None = None,
                 classes: tuple[RequestClass, ...] | None = None):
        if isinstance(plan, FeaturePipeline):
            plan = plan.plan
        if prefetch < 2:
            raise ValueError("FeatureService is double-buffered: prefetch >= 2")
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"bad bucket sizes {buckets!r}")
        if linger_us < 0:
            raise ValueError("linger_us must be >= 0")
        if rebalance_every < 0:
            raise ValueError("rebalance_every must be >= 0")
        if row_budget is not None and row_budget < 32:
            raise ValueError("row_budget must be >= 32 (one alignment word)")
        if hot_factor < 1.0:
            raise ValueError("hot_factor must be >= 1 (hot means above mean)")
        if (rebalance_every or row_budget) and not (sharded and plan.packed):
            raise ValueError("adaptive shard management (rebalance_every / "
                             "row_budget) needs sharded=True over a packed "
                             "plan")
        if hbm_budget_bytes is not None and not (sharded and plan.packed):
            raise ValueError("tiered residency (hbm_budget_bytes) needs "
                             "sharded=True over a packed plan")
        if cold_after < 1:
            raise ValueError("cold_after must be >= 1 monitor tick")
        if host_gather_workers is None:
            # fan-out can only cut the miss window when there are spare
            # cores for the pool to land on; a 1-core host stays sequential
            host_gather_workers = min(4, os.cpu_count() or 1)
        if host_gather_workers < 1:
            raise ValueError("host_gather_workers must be >= 1")
        self.plan = plan
        self.packed = plan.packed
        self.prefetch = prefetch
        self.buckets = tuple(sorted(buckets))
        self.use_kernel = use_kernel
        self.sharded = sharded
        self._linger_s = linger_us * 1e-6
        if sharded and self.packed:
            # mesh mode: per-IMCU word-stream shards, one committed executor
            # + one launch queue/window per shard, all fed by the one pump
            self._sharded_ex = ShardedFeatureExecutor(
                plan, use_kernel=use_kernel, prefetch=prefetch,
                devices=devices, hbm_budget_bytes=hbm_budget_bytes)
            self._executors = self._sharded_ex.executors
            self._executor = self._executors[0]
            self._n_shards = self._sharded_ex.n_shards
        else:
            # ONE executor — device ADV tables are shared; legacy int32
            # sharding only changes where the host code slices come from
            self._sharded_ex = None
            self._executor = FeatureExecutor(plan, use_kernel=use_kernel,
                                             prefetch=prefetch)
            self._executors = [self._executor]
            self._n_shards = 1
        if self._executor.kernel_active:
            # align buckets to the fused kernel's row tile, else every
            # bucket gets padded AGAIN to a bn multiple inside the kernel
            bn = plan.fused_tables().bn
            self.buckets = tuple(sorted(
                {-(-b // bn) * bn for b in self.buckets}))
        elif self.packed:
            # word-aligned buckets keep the range iterator's discipline and
            # one compiled indexed shape per bucket
            self.buckets = tuple(sorted(
                {-(-b // 32) * 32 for b in self.buckets}))
        if sharded and not self.packed:
            self._shard_bounds = plan.imcu_bounds()
            self._shards = plan.imcu_shards()
            self._starts = np.array([b[0] for b in self._shard_bounds])
        if coalesce < 1:
            raise ValueError("coalesce must be >= 1")
        self.coalesce = coalesce if self.packed else 1
        # -- pump-shared state: everything below is guarded by _lock --
        # one launch queue + one in-flight window PER SHARD; each in-flight
        # entry is (device buffer, parts) where each part is
        # (ticket, n_valid_rows, dest, row_off) — row_off is the chunk's
        # start row in the flat (rows, F) launch buffer
        self._queues = [deque() for _ in range(self._n_shards)]
        self._inflights = [deque() for _ in range(self._n_shards)]
        self._busy = [0] * self._n_shards   # launches/retires mid-flight
        self._chunks_total: dict[int, int] = {}
        self._chunks_done: dict[int, int] = {}
        self._ticket_rows: dict[int, int] = {}
        self._out_buf: dict[int, np.ndarray] = {}
        self._results: dict[int, np.ndarray] = {}
        self._claimed: set[int] = set()     # tickets a result() call waits on
        self._next_ticket = 0
        self._submitted_at: dict[int, float] = {}
        self._paused = False
        self._shutdown = False
        self._flushes = 0               # drain()s in progress: no lingering
        self._pump_error: BaseException | None = None
        # -- fault-tolerance state --
        self._faults = faults
        self._policy = fault_policy if fault_policy is not None \
            else FaultPolicy()
        self._errors: dict[int, ServeError] = {}   # failed-ticket results
        self._dead: set[int] = set()    # failed tickets: drop their chunks
        self._deadlines: dict[int, float] = {}     # ticket -> perf_counter
        # breakers key on the executor's STABLE stream token, never id():
        # a dropped replica's id() can be recycled for a fresh executor,
        # which would alias the new stream onto a stale open breaker
        self._breakers: dict[int, StreamBreaker] = {}   # stream_token ->
        self._stream_rr = [0] * self._n_shards     # healthy-stream cursor
        # -- device-loss recovery state --
        self._device_health = DeviceHealth()
        self._needs_rebuild: set[int] = set()   # shards with no live stream
        # -- pump supervisor state (journal: what the pump held when it
        #    died, so a restart re-enqueues instead of losing tickets) --
        self._pump_restarts_used = 0
        self._pump_taken: tuple | None = None      # (shard, group) pre-launch
        self._pump_retiring: tuple | None = None   # (shard, _Flight)
        self._retire_prog = 0       # parts fully retired of current flight
        self._stragglers = [self._new_straggler()
                            for _ in range(self._n_shards)]
        # -- latency accounting --
        # the histograms see every completed ticket and back
        # latency_percentile()/class_stats() — the SLO-gate reading;
        # stats['latency_samples_total'] counts what they cover
        self._lat_hist = LatencyHistogram()
        # -- request classes (priority pump scheduling + per-class SLOs) --
        # every service carries a 'default' class (service-wide coalesce/
        # linger, priority 1, no deadline) so classless submits flow
        # exactly as before; the front door registers real classes here
        self._classes: dict[str, RequestClass] = {
            "default": RequestClass("default")}
        for rc in (classes or ()):
            if rc.name in self._classes and rc.name != "default":
                raise ValueError(f"duplicate request class {rc.name!r}")
            self._classes[rc.name] = rc
        self._ticket_class: dict[int, str] = {}
        self._class_stats: dict[str, dict] = {
            name: {"requests": 0, "completed": 0, "failed": 0, "rows": 0,
                   "hist": LatencyHistogram(), "queue": LatencyHistogram()}
            for name in self._classes}
        # -- adaptive shard management state --
        self.rebalance_every = rebalance_every
        self.row_budget = row_budget
        self.hot_factor = hot_factor
        self.max_replicas = max_replicas
        self._mon_alpha = 0.5           # EWMA weight per monitor tick
        self._mon_ewma = [0.0] * self._n_shards
        self._mon_last = [0] * self._n_shards
        self._mon_mark = 0              # launches at the last monitor tick
        self._route_gen = 0             # bumped on every routing-table swap
        self._admin_q: deque = deque()  # (fn, event, result_box) for the pump
        # -- tiered residency state --
        # construction committed shards in order while the budget lasted;
        # everything the ledger left uncommitted starts WARM
        self.cold_after = cold_after
        self._tier = (["hot" if ex.resident_bytes() > 0 else "warm"
                       for ex in self._executors]
                      if self._sharded_ex is not None
                      else ["hot"] * self._n_shards)
        self._offdevice = {s for s, t in enumerate(self._tier) if t != "hot"}
        self._promote_pending: set[int] = set()   # tier misses awaiting a beat
        self._warm_ticks = [0] * self._n_shards   # quiet ticks while warm
        self._host_served = [0] * self._n_shards  # host-path chunks (EWMA feed)
        self._host_workers = host_gather_workers
        self._host_pool: ThreadPoolExecutor | None = None   # lazy fan-out
        self.stats = {"requests": 0, "rows": 0, "padded_rows": 0,
                      "launched_rows": 0,
                      "batches": 0, "launches": 0, "max_inflight": 0,
                      "latency_s_total": 0.0, "completed": 0,
                      "latency_samples_total": 0,
                      "packed_ranges": 0, "bytes_h2d": 0, "split_requests": 0,
                      "filtered_requests": 0,
                      "retries": 0, "failovers": 0, "timeouts": 0,
                      "failed_tickets": 0, "unhealthy_shards": 0,
                      "stragglers": 0,
                      "recoveries": 0, "pump_restarts": 0,
                      "hedges": 0, "hedge_wins": 0,
                      "devices_lost": 0, "host_gathers": 0,
                      "rebalances": 0, "replicas_added": 0,
                      "replicas_dropped": 0, "shard_splits": 0,
                      "promotions": 0, "demotions": 0, "rehydrations": 0,
                      "tier_misses": 0,
                      "tier_hot": self._tier.count("hot"),
                      "tier_warm": self._tier.count("warm"),
                      "tier_cold": 0,
                      "shard_launches": [0] * self._n_shards,
                      "shard_batches": [0] * self._n_shards,
                      "shard_bytes_h2d": [0] * self._n_shards}
        # conditions over ONE lock, so each event wakes only the threads
        # that care (on small-core hosts a spurious wake steals GIL time
        # from the XLA compute the pumps are trying to overlap):
        #   _work — the pump sleeps here; submits that queued work (and
        #           pause/shutdown/drain-flush) notify
        #   _cv       — result()/poll() waiters; notified when a ticket lands
        #   _idle     — drain() waiters; notified when all pumps go idle
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._cv = threading.Condition(self._lock)
        self._idle = threading.Condition(self._lock)
        self._seq = 0                       # global launch order for retires
        self._pump = threading.Thread(target=self._pump_main,
                                      name="feature-service-pump",
                                      daemon=True)
        self._pump.start()

    # -- lifecycle ------------------------------------------------------------------
    def __enter__(self) -> "FeatureService":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    @property
    def n_shards(self) -> int:
        """Launch streams this service serves through (1 unsharded)."""
        return self._n_shards

    @property
    def replicas(self) -> list[int]:
        """Replica count per shard — the read-fan-out picture the adaptive
        policies produced (all zeros for unsharded services)."""
        if self._sharded_ex is None:
            return [0] * self._n_shards
        return [len(r) for r in self._sharded_ex.replicas]

    @property
    def monitor_ewma(self) -> list[float]:
        """Per-shard request-rate EWMA — the load monitor's current view
        (what :meth:`rebalance` decides replicate/shed/split from)."""
        return list(self._mon_ewma)

    @property
    def shard_starts(self) -> list[int]:
        """Routing-table row starts per shard (grows on tail splits)."""
        if self._sharded_ex is None:
            return [0]
        return list(self._sharded_ex._routing[1])

    def shutdown(self, drain: bool = True) -> None:
        """Stop every pump thread and join them.

        ``drain=True`` (default) serves everything already queued first (an
        orderly drain — results stay retrievable via :meth:`result` /
        :meth:`drain`); ``drain=False`` discards queued-but-unlaunched
        chunks, forgetting their tickets. Idempotent.
        """
        with self._lock:
            if not drain:
                dropped = set()
                for q in self._queues:
                    dropped.update(ch.ticket for ch in q)
                    q.clear()
                for t in dropped:
                    self._chunks_total.pop(t, None)
                    self._chunks_done.pop(t, None)
                    self._ticket_rows.pop(t, None)
                    self._out_buf.pop(t, None)
                    self._submitted_at.pop(t, None)
                    self._deadlines.pop(t, None)
                    self._ticket_class.pop(t, None)
            self._shutdown = True
            self._notify_everyone()
        self._pump.join()
        if self._host_pool is not None:
            self._host_pool.shutdown(wait=True)   # idempotent

    def _notify_everyone(self) -> None:
        """Wake every waiter class (lock held) — shutdown/error paths."""
        self._work.notify_all()
        self._cv.notify_all()
        self._idle.notify_all()

    def _check_pump(self) -> None:
        if self._pump_error is not None:
            raise RuntimeError("feature-service pump thread died") \
                from self._pump_error

    def pause(self) -> None:
        """Hold launches (submissions still queue) — lets a caller batch a
        burst of submits into maximally coalesced launches."""
        with self._lock:
            self._check_pump()
            self._paused = True
            self._work.notify_all()

    def resume(self) -> None:
        with self._lock:
            self._check_pump()
            self._paused = False
            self._work.notify_all()

    # -- fault tolerance: breakers, stream health, failure handling ------------------
    def _new_straggler(self) -> StragglerDetector:
        p = self._policy
        return StragglerDetector(threshold=p.straggler_threshold,
                                 warmup=p.straggler_warmup)

    def _breaker(self, ex) -> StreamBreaker:
        b = self._breakers.get(ex.stream_token)
        if b is None:
            b = self._breakers[ex.stream_token] = StreamBreaker()
        return b

    def _close_breaker_locked(self, ex, now: float) -> None:
        """A round trip proved the stream healthy: close its breaker, and
        when it was TRIPPED, give back its ``unhealthy_shards`` mark —
        the stat is a gauge of currently-unhealthy streams, not a
        lifetime trip counter. A success while the breaker is still OPEN
        does NOT close it: a shard whose only stream tripped keeps
        launching through the open breaker, and those forced launches are
        not probes — the breaker holds until the cooldown makes the
        stream half-open and a success there is the real probe."""
        b = self._breakers.get(ex.stream_token)
        if b is None or b.is_open(self._policy.breaker_fails, now):
            return
        if b.fails >= self._policy.breaker_fails:
            self.stats["unhealthy_shards"] -= 1
        b.reset()

    def _discard_breaker_locked(self, ex) -> None:
        """The stream is leaving the shard set (replica drop, device
        eviction, rebuild swap): forget its breaker — and give back its
        gauge mark when it left unhealthy. Without this, breakers leak
        per dropped stream (and a recycled executor id could inherit a
        stale open breaker — tokens make that structural, this makes the
        table size match the live stream set)."""
        b = self._breakers.pop(ex.stream_token, None)
        if b is not None and b.fails >= self._policy.breaker_fails:
            self.stats["unhealthy_shards"] -= 1

    def _shard_streams(self, s: int) -> list:
        return (self._sharded_ex.stream_executors(s)
                if self._sharded_ex is not None else [self._executor])

    def _healthy_streams(self, s: int, now: float) -> list:
        thr = self._policy.breaker_fails
        return [ex for ex in self._shard_streams(s)
                if not self._breaker(ex).is_open(thr, now)
                and not self._device_health.is_down(id(ex.device))]

    @property
    def unhealthy(self) -> list[int]:
        """Shards with at least one OPEN-breaker launch stream right now —
        what the monitor's failover policy re-replicates around."""
        with self._lock:
            now = time.perf_counter()
            return [s for s in range(self._n_shards)
                    if len(self._healthy_streams(s, now))
                    < len(self._shard_streams(s))]

    def _pick_stream(self, s: int, avoid: frozenset):
        """Healthy-stream selection with read fan-out (pump thread, lock
        held). Round-robins the shard's closed-breaker streams; a stream
        past its breaker cooldown is half-open and its next pick is the
        recovery probe. ``avoid`` (executor ids a retrying group already
        failed on) is excluded unless nothing else is left — a retry
        prefers a replica it has NOT watched fail. Returns (executor,
        stream index)."""
        streams = self._shard_streams(s)
        if len(streams) == 1 and not avoid:
            return streams[0], 0
        now = time.perf_counter()
        thr = self._policy.breaker_fails
        dh = self._device_health
        idx = list(range(len(streams)))
        healthy = [i for i in idx
                   if not self._breaker(streams[i]).is_open(thr, now)
                   and not dh.is_down(id(streams[i].device))]
        pool = ([i for i in healthy
                 if streams[i].stream_token not in avoid]
                or healthy
                or [i for i in idx if streams[i].stream_token not in avoid]
                or idx)
        self._stream_rr[s] += 1
        i = pool[self._stream_rr[s] % len(pool)]
        return streams[i], i

    def _strike_locked(self, ex, shard: int, now: float) -> bool:
        """One failure (or straggler flag) on a stream: breaker
        bookkeeping + the unhealthy-shard mark the monitor keys on.
        Returns True when this strike TRIPPED the breaker — the event
        device-loss attribution counts."""
        p = self._policy
        if self._breaker(ex).strike(p.breaker_fails, p.breaker_cooldown_s,
                                    now):
            self.stats["unhealthy_shards"] += 1
            return True
        return False

    def _observe_latency_locked(self, s: int, ex, dt: float,
                                now: float) -> None:
        """Feed the shard's straggler detector with one launch round-trip
        time; a flagged launch that also clears the absolute floor counts
        as a breaker strike (slow stream -> same unhealthy/re-replicate
        path as a failing one), otherwise the round trip proves the
        stream healthy and closes its breaker."""
        flagged = self._stragglers[s].observe(
            self.stats["shard_launches"][s], dt)
        if flagged and dt >= self._policy.straggler_min_s:
            self.stats["stragglers"] += 1
            self._strike_locked(ex, s, now)
        else:
            self._close_breaker_locked(ex, now)
            self._device_health.ok(id(ex.device))

    def _fail_ticket_locked(self, ticket: int, err: ServeError, *,
                            timeout: bool = False) -> None:
        """Resolve ``ticket`` to a typed error (lock held): the ledger
        entries go, the error is retrievable via poll/result/collect, and
        chunks of this ticket still queued anywhere are dropped on sight
        (``_dead``). Idempotent for already-resolved tickets."""
        if ticket not in self._chunks_total:
            return
        del self._chunks_total[ticket]
        self._chunks_done.pop(ticket, None)
        self._ticket_rows.pop(ticket, None)
        self._out_buf.pop(ticket, None)
        self._deadlines.pop(ticket, None)
        self._submitted_at.pop(ticket, None)
        self._dead.add(ticket)
        self._errors[ticket] = err
        self.stats["failed_tickets"] += 1
        k = self._ticket_class.pop(ticket, None)
        if k is not None:
            self._class_stats[k]["failed"] += 1
        if timeout:
            self.stats["timeouts"] += 1
        self._cv.notify_all()

    def _handle_launch_failure(self, s: int, group: list[_Chunk], ex,
                               err: Exception) -> None:
        """Fault isolation (lock held, pump thread): one launch group's
        failure touches ONLY its own chunks. Strike the stream's breaker,
        then re-enqueue the group at the head of its shard's queue —
        immediately when another healthy stream can take the retry
        (replica failover), else after capped exponential backoff.
        Chunks out of retries resolve their tickets to ServeError.

        Device attribution: a breaker TRIP counts one strike against the
        stream's device; a :class:`DeviceDown` error declares it dead
        outright. A newly-dead device triggers recovery (evict + rebuild
        elsewhere) before the group is re-enqueued, so the retry already
        sees the post-eviction stream set."""
        now = time.perf_counter()
        tripped = self._strike_locked(ex, s, now)
        if self._sharded_ex is not None and ex.device is not None:
            dev_id = id(ex.device)
            if isinstance(err, DeviceDown):
                newly_down = self._device_health.mark_down(dev_id)
            elif tripped:
                newly_down = self._device_health.strike(
                    dev_id, self._policy.device_fails)
            else:
                newly_down = False
            if newly_down:
                self._recover_device_locked(dev_id)
        retry = [ch for ch in group
                 if ch.attempts + 1 <= self._policy.max_retries
                 and ch.ticket not in self._dead]
        failed = [ch for ch in group if ch not in retry]
        for ch in failed:
            self._fail_ticket_locked(ch.ticket, ServeError(
                f"request failed after {ch.attempts + 1} launch attempts "
                f"on shard {s}: {err!r}", ticket=ch.ticket, shard=s,
                attempts=ch.attempts + 1))
            self._errors[ch.ticket].__cause__ = err
        if not retry:
            return
        failed_tok = ex.stream_token
        alt = any(e.stream_token != failed_tok
                  for e in self._healthy_streams(s, now))
        for ch in reversed(retry):
            ch.attempts += 1
            ch.avoid = ch.avoid | {failed_tok}
            ch.not_before = now if alt \
                else now + self._policy.backoff_for(ch.attempts)
            self._queues[s].appendleft(ch)
        self.stats["retries"] += 1
        self._work.notify_all()

    # -- device-loss recovery (evict -> host-serve -> rebuild) -----------------------
    def _recover_device_locked(self, dev_id: int) -> None:
        """A device was declared dead (lock held, pump thread): evict
        every resident stream on it — replicas dropped, orphaned
        primaries promoted from surviving replicas — and mark shards
        left with NO live stream for emergency rebuild. Their queued
        work is served through the host-gather slow path until the
        rebuild lands (:meth:`_pick_action` policy: hostserve before
        launch for marked shards)."""
        self.stats["devices_lost"] += 1
        removed, orphans = self._sharded_ex.evict_device(dev_id)
        for _s, rex in removed:
            self._discard_breaker_locked(rex)
        for s in orphans:
            # a shard the tier ladder already demoted was host-served
            # before the device died — no emergency rebuild; promotion
            # (if its load comes back) rebuilds on a survivor
            if s in self._offdevice:
                continue
            self._needs_rebuild.add(s)
        self._work.notify_all()

    def _rebuild_shard_locked(self, s: int) -> bool:
        """Emergency rebuild of an orphaned shard's stream on a surviving
        device (lock held, pump thread). False (shard stays host-served)
        when no device survives; True when the fresh stream is committed
        — from then on the shard launches normally again."""
        sx = self._sharded_ex
        lost = set(self._device_health.down)
        old = sx.executors[s]
        try:
            sx.rebuild_on(s, lost=lost)
        except ValueError:
            return False                 # nothing healthy to rebuild on
        self._discard_breaker_locked(old)
        self._needs_rebuild.discard(s)
        self.stats["recoveries"] += 1
        self._work.notify_all()
        return True

    def _host_features_group(self, s: int, group: list) -> list[np.ndarray]:
        """Compute a host-gather group's features (pump thread, NO lock
        held): one :meth:`FeaturePlan.host_features` per chunk — the same
        codes and the same OOB clamp as the device gather, so results are
        bit-exact — fanned out over a small lazy thread pool so a multi-
        chunk miss window costs ~one gather of wall time instead of
        ``len(group)``. Single-chunk groups (and ``host_gather_workers=1``)
        skip the pool. Safe concurrently: per-column word/RLE reads are
        pure, and the caches the gathers may populate are idempotent
        (equal values; last write wins). Tier mutations can't race — they
        run only on the pump thread, which is blocked here."""
        plan = (self._sharded_ex.shards[s]
                if self._sharded_ex is not None else self.plan)
        if len(group) == 1 or self._host_workers == 1:
            return [plan.host_features(ch.rows) for ch in group]
        if self._host_pool is None:
            self._host_pool = ThreadPoolExecutor(
                max_workers=self._host_workers,
                thread_name_prefix="feature-service-hostgather")
        return list(self._host_pool.map(
            lambda ch: plan.host_features(ch.rows), group))

    def _host_serve(self, s: int, group: list) -> None:
        """Serve one taken host-gather group end to end (pump thread, lock
        NOT held on entry): degraded-mode serving for shards with no live
        stream (device loss) and the TIER-MISS path for warm/cold shards.
        Never double-counts launch stats — only ``host_gathers`` (and
        ``tier_misses`` when the shard is off-device by tier rather than
        loss; a miss also marks the shard promotion-pending, the async
        promotion the pump picks up on a free beat). Crash-safe via the
        ``_pump_taken`` journal: a chunk leaves the journaled group only
        after its retire completes, so a pump restart re-serves exactly
        the unserved tail."""
        feats_list = self._host_features_group(s, group)
        with self._lock:
            self.stats["host_gathers"] += 1
            self._host_served[s] += len(group)
            miss = s in self._offdevice and s not in self._needs_rebuild
            if miss:
                self.stats["tier_misses"] += 1
                self._warm_ticks[s] = 0
                self._promote_pending.add(s)
            landed = False
            for feats in feats_list:
                ch = group[0]
                self._retire_prog = 0
                if self._retire(feats, [(ch.ticket, ch.n, ch.dest, 0)]):
                    landed = True
                del group[0]
            if landed:
                self._cv.notify_all()
            self._pump_taken = None
            self._busy[s] -= 1
            if self.rebalance_every and (
                    self.stats["launches"]
                    + self.stats["host_gathers"] - self._mon_mark
                    >= self.rebalance_every):
                self._rebalance_locked()
            if miss:
                self._work.notify_all()   # the promote arm has work now
            if self._all_idle():
                self._idle.notify_all()

    # -- request intake -------------------------------------------------------------
    def _route(self, rows: np.ndarray, lo: int, hi: int):
        """(shard, local_rows, dest) pieces for a request's rows.

        Single-pump services own everything in shard 0 (dest None = whole
        request in order). Multi-shard packed services bucket by owning
        IMCU — the clustered fast path (all rows in one shard, the common
        'per-user block' lookup) routes without materializing an index.
        """
        if self._n_shards == 1:
            return [(0, rows, None)]
        return self._sharded_ex.route(rows, lo, hi)

    def submit(self, rows: np.ndarray | None = None, *, where=None,
               deadline_ms: float | None = None,
               klass: str = "default") -> int:
        """Enqueue a featurization request; returns a ticket for the result.

        Only queues: the background pumps pick the chunks up, coalesce them
        with other queued work owned by the same shard and launch — the
        caller goes on submitting while the devices gather.

        ``where=<predicate>`` (instead of explicit ``rows``) is the
        pushdown form: the matching rows are found by the device-side
        predicate scan over the resident word streams (per shard on a mesh
        service) and then pumped through the SAME coalescing launch path as
        any explicit request — "serve features WHERE ..." as one ticket.

        ``deadline_ms`` bounds the request's time in the system: chunks
        still QUEUED once it expires are dropped before launch and the
        ticket resolves to :class:`DeadlineExceeded` (chunks already in
        flight retire normally — a deadline evicts queued work, it does
        not cancel device work).

        ``klass`` names a registered :class:`RequestClass` (construct the
        service with ``classes=``): it sets the pump's scheduling
        priority, coalescing policy and — when ``deadline_ms`` is not
        passed — the class's default deadline.
        """
        with span("serve.submit", klass=klass) as sp:
            return self._submit(sp, rows, where, deadline_ms, klass)

    def _submit(self, sp, rows, where, deadline_ms, klass: str) -> int:
        """:meth:`submit` inside its ``serve.submit`` span ``sp``, which
        gets the ticket and the request's row count."""
        rc = self._classes.get(klass)
        if rc is None:
            raise ValueError(f"unknown request class {klass!r} "
                             f"(registered: {sorted(self._classes)})")
        if deadline_ms is None:
            deadline_ms = rc.deadline_ms
        if deadline_ms is not None and deadline_ms <= 0:
            raise ValueError("deadline_ms must be > 0")
        filtered = where is not None
        if filtered:
            if rows is not None:
                raise ValueError("pass rows OR where, not both")
            if not self.packed:
                raise RuntimeError("predicate-filtered serving needs a "
                                   "packed plan (resident word streams)")
            ex = self._sharded_ex if self._sharded_ex is not None \
                else self._executor
            rows = ex.filtered_rows(where)
            if rows.size == 0:
                # empty selection: nothing to pump — mint a ticket whose
                # (0, F) result is already on host (poll/result check the
                # results map before the chunk ledger, so this short-
                # circuit needs no pump cooperation)
                with self._lock:
                    self._check_pump()
                    if self._shutdown:
                        raise RuntimeError("service is shut down")
                    ticket = self._next_ticket
                    self._next_ticket += 1
                    self.stats["requests"] += 1
                    self.stats["filtered_requests"] += 1
                    self.stats["completed"] += 1
                    cs = self._class_stats[klass]
                    cs["requests"] += 1
                    cs["completed"] += 1
                    self._results[ticket] = np.zeros(
                        (0, self.plan.out_dim), np.float32)
                    self._cv.notify_all()
                sp.set_metadata(ticket=ticket, rows=0)
                return ticket
        elif rows is None:
            raise ValueError("need rows or where")
        rows = np.asarray(rows, dtype=np.int64).reshape(-1)
        if rows.size == 0:
            raise ValueError("empty request")
        lo, hi = int(rows.min()), int(rows.max())
        if lo < 0 or hi >= self.plan.n_rows:
            raise IndexError(f"row indices out of range [0, {self.plan.n_rows})")
        # routing, chunking and the O(chunk) alignment scan are pure
        # functions of the request — do them OUTSIDE the lock. A pump-side
        # rebalance may swap the routing table between that work and the
        # enqueue below; the generation check catches it and reroutes (a
        # chunk built against stale bounds would land on a shard that no
        # longer owns its rows)
        cap = self.buckets[-1]
        while True:
            gen = self._route_gen
            pieces, padded, aligned = [], 0, 0
            routed = self._route(rows, lo, hi)
            for shard, local, dest in routed:
                for start in range(0, local.shape[0], cap):
                    chunk = local[start:start + cap]
                    bucket = self._bucket(chunk.shape[0])
                    padded += bucket - chunk.shape[0]
                    if self.packed and self._aligned_range(chunk):
                        aligned += 1
                    d = start if dest is None else dest[start:start + cap]
                    pieces.append(_Chunk(0, chunk, chunk.shape[0], bucket,
                                         shard, d))
            with self._lock:
                self._check_pump()
                if self._shutdown:
                    raise RuntimeError("service is shut down")
                if self._route_gen != gen:
                    continue            # routing swapped mid-build: redo
                ticket = self._next_ticket
                self._next_ticket += 1
                now = time.perf_counter()
                self._submitted_at[ticket] = now
                if deadline_ms is not None:
                    self._deadlines[ticket] = now + deadline_ms / 1e3
                self.stats["requests"] += 1
                self.stats["rows"] += rows.size
                self.stats["padded_rows"] += padded
                self.stats["packed_ranges"] += aligned
                if filtered:
                    self.stats["filtered_requests"] += 1
                if len(routed) > 1:
                    self.stats["split_requests"] += 1
                self._chunks_total[ticket] = len(pieces)
                self._ticket_rows[ticket] = rows.size
                self._ticket_class[ticket] = klass
                cs = self._class_stats[klass]
                cs["requests"] += 1
                cs["rows"] += rows.size
                before = {}
                for ch in pieces:
                    ch.ticket = ticket
                    ch.t_enq = now
                    ch.klass = klass
                    q = self._queues[ch.shard]
                    before.setdefault(ch.shard, len(q))
                    q.append(ch)
                for s, n0 in before.items():
                    # wake discipline (each wake steals GIL time from XLA):
                    # the parked pump needs a wake when a shard queue goes
                    # empty -> nonempty (to start serving, or arm its linger
                    # timer), when this submit completed a coalescing
                    # group, or when it OUTRANKS the queue's current head —
                    # a lingering low-priority group must not make a
                    # fresh high-priority chunk wait out its hold; chunks
                    # landing mid-group otherwise ride the pending tick
                    q = self._queues[s]
                    n1 = len(q)
                    preempt = n0 > 0 and rc.priority > \
                        self._classes[q[0].klass].priority
                    if n0 == 0 or preempt or (n0 < self.coalesce <= n1):
                        self._work.notify_all()
                        break
            sp.set_metadata(ticket=ticket, rows=rows.size)
            return ticket

    # -- bucketing ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        """Smallest static bucket >= n (largest bucket caps a chunk)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _slice_padded(self, rows: np.ndarray, bucket: int) -> np.ndarray:
        """Host work for one int32 chunk: fancy-index + right-pad to bucket."""
        rows = pad_rows_edge(rows, bucket)
        if self.sharded and not self.packed:
            return self._gather_sharded_codes(rows)
        return self.plan.host_codes(rows)

    def _gather_sharded_codes(self, rows: np.ndarray) -> np.ndarray:
        """Route rows to their owning IMCU partitions (partition-local
        slices) — the legacy int32 sharding, where only the HOST side is
        partitioned and one pump still serves every launch.

        Rows appended after plan compile (streaming inserts via
        ``FeaturePlan.refresh``) live past the last IMCU boundary and are
        served from the plan's own code matrix tail.
        """
        out = np.empty((len(self.plan.plans), rows.shape[0]), np.int32)
        tail_start = self._shard_bounds[-1][1]
        tail = rows >= tail_start
        if tail.any():
            out[:, tail] = self.plan.codes_matrix[:, rows[tail]]
        rows_in, (idx_in,) = rows[~tail], np.nonzero(~tail)
        shard_of = np.searchsorted(self._starts, rows_in, side="right") - 1
        for s in np.unique(shard_of):
            mask = shard_of == s
            local = rows_in[mask] - self._shard_bounds[s][0]
            out[:, idx_in[mask]] = self._shards[s].codes_matrix[:, local]
        return out

    @staticmethod
    def _aligned_range(rows: np.ndarray) -> bool:
        """True for a word-aligned contiguous run (the scan pattern) —
        tracked in ``stats['packed_ranges']``; served by the same unified
        indexed launch as arbitrary row sets. The O(1) prefix checks gate
        the O(n) scan: this runs on every submit."""
        if rows.shape[0] == 0 or int(rows[0]) % 32 or \
                int(rows[-1]) - int(rows[0]) != rows.shape[0] - 1:
            return False
        return bool((np.diff(rows) == 1).all())

    # -- the background pumps ---------------------------------------------------------
    def _coalesce_for(self, rc: RequestClass) -> int:
        """Effective coalescing depth for one class: the class's own when
        set, else the service-wide depth — capped at the service depth
        either way and forced to 1 on unpacked plans (no coalesced
        launches there). A launch of the class gathers this many lanes
        (:meth:`_lanes`)."""
        if not self.packed:
            return 1
        c = rc.coalesce if rc.coalesce is not None else self.coalesce
        return max(1, min(c, self.coalesce))

    def _lanes(self, group: list[_Chunk]) -> int:
        """Lanes one launch of ``group`` gathers: its class's coalesce
        depth, so each (class depth, bucket) pair is one compiled shape
        and a singleton class gathers and copies one lane, not the
        service-wide depth."""
        return self._coalesce_for(self._classes[group[0].klass])

    def _linger_for(self, rc: RequestClass) -> float:
        return rc.linger_us * 1e-6 if rc.linger_us is not None \
            else self._linger_s

    def _select_class(self, queue: deque, now: float):
        """Pick the request class shard ``queue`` serves next (lock held).

        Scores each class PRESENT in the queue by its oldest chunk:
        ``priority + waited / aging_s`` — static priority plus
        anti-starvation aging, so a starving ``background`` head
        eventually outranks a fresh ``interactive`` one and low-priority
        work always drains. Classes whose head chunk is still in retry
        backoff are not candidates. Returns ``(klass, head, 0.0)`` for
        the winner, or ``(None, None, hold)`` when every present class is
        backing off (``hold`` = seconds until the nearest backoff ends,
        the caller's wait bound). O(queue) with early exit once every
        registered class was seen.
        """
        heads: dict[str, _Chunk] = {}
        n_classes = len(self._classes)
        for ch in queue:
            if ch.klass not in heads:
                heads[ch.klass] = ch
                if len(heads) == n_classes:
                    break
        best = best_head = None
        best_eff = 0.0
        hold = None
        for name, ch in heads.items():
            if ch.not_before > now:
                h = ch.not_before - now
                hold = h if hold is None else min(hold, h)
                continue
            rc = self._classes[name]
            eff = rc.priority + (now - ch.t_enq) / rc.aging_s
            if best is None or eff > best_eff:
                best, best_head, best_eff = name, ch, eff
        if best is None:
            return None, None, hold if hold is not None else 0.0
        return best, best_head, 0.0

    def _linger_left(self, queue: deque, klass: str, head: _Chunk,
                     now: float) -> float:
        """Seconds the selected class's head launch group should stay
        open. 0 when the group is already full (the CLASS's coalesce
        depth of same-bucket chunks queued) or the head chunk has aged
        past the class's linger deadline — lingering trades a BOUNDED
        latency for fuller groups, it never holds work indefinitely."""
        rc = self._classes[klass]
        cap = self._coalesce_for(rc)
        n_match = 0
        for ch in queue:
            if ch.klass == klass and ch.bucket == head.bucket:
                n_match += 1
                if n_match >= cap:
                    return 0.0
        return head.t_enq + self._linger_for(rc) - now

    def _all_idle(self) -> bool:
        return not any(q or i or b for q, i, b in
                       zip(self._queues, self._inflights, self._busy))

    def _streams(self, s: int) -> int:
        """Launch streams serving shard s (1 + replicas). Each stream gets
        its own ``prefetch``-deep in-flight window: read fan-out scales a
        hot shard's aggregate window with its replica count."""
        return self._sharded_ex.n_streams(s) if self._sharded_ex else 1

    def _pick_action(self):
        """Choose the pump's next action (lock held).

        Returns ``("launch", shard)``, ``("retire", shard)``,
        ``("hostserve", shard)`` (queued work on a shard with no live
        stream — serve it from host words), ``("rebuild", shard)``
        (re-commit an orphaned shard's stream on a surviving device),
        ``("wait", timeout)`` or ``("exit", None)``. Preference order
        keeps every shard's launch stream busy: launch wherever a window
        has room and a group is ready; otherwise retire the OLDEST
        in-flight launch — from a full-window shard first (unblocks its
        stream), else any. Lingering shards (partial group, young head
        chunk) are skipped for launching but their deadline bounds the
        wait timeout, so fuller groups never cost unbounded latency.
        Rebuilds run when nothing is launchable or retirable — and only
        when a device actually survives, so a fully-dead mesh settles
        into pure host-serving instead of spinning.
        """
        held = self._paused and not self._shutdown
        linger_min = None
        now = time.perf_counter()
        for s in range(self._n_shards):
            queue = self._queues[s]
            if not queue or held:
                continue
            if s in self._needs_rebuild or s in self._offdevice:
                return "hostserve", s
            if len(self._inflights[s]) >= self.prefetch * self._streams(s):
                continue
            klass, head, hold = self._select_class(queue, now)
            if klass is None:
                # every queued class's head is backing off after a failed
                # launch: bound the wait like a linger deadline and skip
                linger_min = hold if linger_min is None \
                    else min(linger_min, hold)
                continue
            rc = self._classes[klass]
            if self._linger_for(rc) > 0 and self._coalesce_for(rc) > 1 \
                    and not self._shutdown and not self._flushes:
                left = self._linger_left(queue, klass, head, now)
                if left > 0:
                    linger_min = left if linger_min is None \
                        else min(linger_min, left)
                    continue
            return "launch", s
        # nothing launchable: retire the globally oldest in-flight entry,
        # preferring a shard whose full window is damming its queue
        oldest, oldest_full = None, None
        for s in range(self._n_shards):
            infl = self._inflights[s]
            if not infl:
                continue
            seq = infl[0][0]
            if oldest is None or seq < self._inflights[oldest][0][0]:
                oldest = s
            if len(infl) >= self.prefetch * self._streams(s) and (
                    oldest_full is None
                    or seq < self._inflights[oldest_full][0][0]):
                oldest_full = s
        if oldest_full is not None:
            return "retire", oldest_full
        if oldest is not None and linger_min is None:
            return "retire", oldest
        if self._needs_rebuild and not self._shutdown \
                and self._sharded_ex is not None:
            down = self._device_health.down
            if any(id(d) not in down
                   for d in self._sharded_ex.device_pool):
                return "rebuild", min(self._needs_rebuild)
        if self._promote_pending and not held and not self._shutdown \
                and self._sharded_ex is not None:
            # async promotion on a free beat: hottest pending miss first.
            # Never blocks a request — misses keep host-serving while the
            # re-put runs, and a failed attempt (no budget headroom yet)
            # just clears pending until the next miss re-marks it
            return "promote", max(self._promote_pending,
                                  key=lambda i: self._mon_ewma[i])
        if self._shutdown and self._all_idle() and not self._admin_q:
            return "exit", None
        return "wait", linger_min

    def _pump_main(self) -> None:
        """Pump SUPERVISOR (the thread target): run the pump loop, and
        when it dies of a pump-infrastructure exception — control logic,
        not a guarded launch — restart it with the ledger intact
        (:meth:`_recover_pump_locked` re-enqueues whatever the dying
        pump held mid-operation), up to ``FaultPolicy.pump_restarts``
        times. Past the budget the crash is terminal: ``_pump_error``
        poisons the service and every waiter is unblocked, exactly the
        pre-supervisor behavior."""
        while True:
            try:
                self._pump_loop()
                return
            except BaseException as e:
                with self._lock:
                    if self._pump_restarts_used >= \
                            self._policy.pump_restarts:
                        self._pump_error = e
                        self._fail_admin(e)
                        self._notify_everyone()
                        return
                    self._pump_restarts_used += 1
                    self.stats["pump_restarts"] += 1
                    self._recover_pump_locked()

    def _recover_pump_locked(self) -> None:
        """Restore the ledger's invariants after a pump crash (lock
        held): clear the busy markers the dying pump still held, and put
        back — at the head of its shard's queue, original order — any
        group it had taken for launch but not recorded in flight, plus
        the not-yet-distributed chunks of a retire it was mid-way
        through (parts already distributed stay distributed; the journal
        ``_retire_prog`` marks the boundary). Tickets, queues, in-flight
        windows and the admin queue all survive as-is; blocking entry
        points poll on 0.5 s ticks and behave identically across the
        restart."""
        self._busy = [0] * self._n_shards
        taken = self._pump_taken
        if taken is not None:
            s, group = taken
            for ch in reversed(group):
                self._queues[s].appendleft(ch)
            self._pump_taken = None
        retp = self._pump_retiring
        if retp is not None:
            s, fl = retp
            for i in range(len(fl.group) - 1, self._retire_prog - 1, -1):
                ch = fl.group[i]
                if ch.ticket in self._chunks_total:
                    self._queues[s].appendleft(ch)
            self._pump_retiring = None
        self._work.notify_all()

    def _pump_loop(self) -> None:
        """ONE multiplexing pump drains every shard's queue until shutdown:
        coalesce -> launch -> retire, with a ``prefetch``-deep in-flight
        window PER SHARD. The only thread that dispatches device work or
        blocks on device buffers; shards' launches are dispatched
        asynchronously onto their own devices, so independent shards
        compute concurrently while the pump runs ahead — one thread feeding
        N launch streams (threads-per-shard would fight it for the GIL;
        dispatch is the cheap part).

        Wake discipline: the pump only notifies ``_cv`` when a ticket's
        result actually landed and ``_idle`` when no shard has anything
        left to do — launching and window churn wake nobody, so client
        threads stay parked (and off the GIL) while the devices work.

        Fault isolation: the device-facing work — dispatching a launch and
        blocking on its buffer at retire — is guarded per launch group. An
        exception there routes through :meth:`_handle_launch_failure`
        (retry with backoff, replica failover, per-ticket ServeError) and
        the loop continues; the pump's own control logic raising lands in
        the supervisor (:meth:`_pump_main`) — restart with the ledger
        intact while the budget lasts, terminal after.
        """
        while True:
            with self._lock:
                idle = None         # one pump.wait span per idle stretch
                while True:
                    # shard-set mutations happen HERE — the pump is the
                    # only launcher, and at this point no launch or
                    # retire is mid-flight, so a split/replica swap can
                    # never race a dispatch against stale routing
                    self._drain_admin()
                    action, arg = self._pick_action()
                    if action != "wait":
                        break
                    if idle is None:
                        idle = span("pump.wait")
                        idle.__enter__()
                    if self._all_idle():
                        self._idle.notify_all()
                    self._work.wait(timeout=arg)
                if idle is not None:
                    idle.__exit__(None, None, None)
                if action == "exit":
                    return
                s = arg
                if action == "hostserve":
                    # degraded/off-device mode — the shard has no live
                    # stream (device loss) or lives in a warm/cold tier.
                    # Retry backoffs are void (the host path cannot fail
                    # the way a launch did): take everything queued, then
                    # gather OUTSIDE the lock (thread-pool fan-out) and
                    # retire, journaled like a launch
                    for ch in self._queues[s]:
                        ch.not_before = 0.0
                    hjob = self._take_group(self._queues[s],
                                            time.perf_counter())
                    if not hjob:
                        if self._all_idle():
                            self._idle.notify_all()
                        continue
                    self._pump_taken = (s, hjob)
                    self._busy[s] += 1
                elif action == "rebuild":
                    self._rebuild_shard_locked(s)
                    continue
                elif action == "promote":
                    # pending is cleared WHATEVER the outcome: a promotion
                    # that could not fit leaves the shard warm/cold and the
                    # next tier miss re-marks it — no spinning on a full
                    # device, no lost promotions
                    self._try_promote_locked(s)
                    self._promote_pending.discard(s)
                    if self._all_idle():
                        self._idle.notify_all()
                    continue
                elif action == "launch":
                    # pump.launch runs from the take to the stats update
                    # after the dispatch, across the lock
                    launch = span("pump.launch", shard=s)
                    launch.__enter__()
                    job = self._take_group(self._queues[s],
                                           time.perf_counter())
                    if not job:
                        # the whole head group was evicted (failed or
                        # deadline-expired tickets) — nothing to launch
                        launch.__exit__(None, None, None)
                        if self._all_idle():
                            self._idle.notify_all()
                        continue
                    self._pump_taken = (s, job)
                    ex, _stream = self._pick_stream(s, job[0].avoid)
                    if job[0].avoid and \
                            ex.stream_token not in job[0].avoid:
                        # a retry actually reached a stream it had not
                        # failed on yet: replica failover
                        self.stats["failovers"] += 1
                else:
                    job = None
                    seq, fl = self._inflights[s].popleft()
                    retire = span("pump.retire", seq=seq)
                    retire.__enter__()
                    if retire is not OFF:
                        retire.set_metadata(
                            tickets=ids(p[0] for p in fl.parts))
                    self._pump_retiring = (s, fl)
                    self._retire_prog = 0
                if action != "hostserve":
                    self._busy[s] += 1
            if action == "hostserve":
                # gather + retire outside the lock (the pool does the
                # per-chunk host_features); crash-safe via _pump_taken
                self._host_serve(s, hjob)
                continue
            if job is not None:
                t0 = time.perf_counter()
                try:
                    dev, parts, nbytes, stall = self._launch(job, s, ex,
                                                             _stream)
                except Exception as e:
                    with self._lock:
                        self._handle_launch_failure(s, job, ex, e)
                        self._pump_taken = None
                        self._busy[s] -= 1
                        if self._all_idle():
                            self._idle.notify_all()
                    launch.__exit__(None, None, None)
                    continue
                with self._lock:
                    self._seq += 1
                    self._inflights[s].append((self._seq, _Flight(
                        dev, parts, job, ex, t0,
                        ready_at=t0 + stall if stall else 0.0)))
                    self._pump_taken = None
                    self.stats["launches"] += 1
                    self.stats["batches"] += len(parts)
                    lanes = self._lanes(job)
                    self.stats["launched_rows"] += lanes * job[0].bucket
                    self.stats["bytes_h2d"] += nbytes
                    self.stats["shard_launches"][s] += 1
                    self.stats["shard_batches"][s] += len(parts)
                    self.stats["shard_bytes_h2d"][s] += nbytes
                    self.stats["max_inflight"] = max(
                        self.stats["max_inflight"],
                        sum(len(i) for i in self._inflights))
                    self._busy[s] -= 1
                    if launch is not OFF:
                        launch.set_metadata(
                            seq=self._seq, klass=job[0].klass,
                            bucket=job[0].bucket, lanes_used=len(job),
                            lanes=lanes,
                            tickets=ids(ch.ticket for ch in job))
                    launch.__exit__(None, None, None)
                    if self.rebalance_every and (
                            self.stats["launches"]
                            + self.stats["host_gathers"] - self._mon_mark
                            >= self.rebalance_every):
                        self._rebalance_locked()
            else:
                try:
                    arr, win_ex, dt, by_hedge = self._await_flight(s, fl,
                                                                   seq)
                except Exception as e:
                    with self._lock:
                        self._handle_launch_failure(s, fl.group, fl.ex, e)
                        self._pump_retiring = None
                        self._busy[s] -= 1
                        if self._all_idle():
                            self._idle.notify_all()
                    retire.__exit__(None, None, None)
                    continue
                with self._lock:
                    now = time.perf_counter()
                    self._observe_latency_locked(s, win_ex, dt, now)
                    if by_hedge:
                        # the primary lost a race against its own
                        # duplicate — that IS a straggler strike
                        self.stats["hedge_wins"] += 1
                        self._strike_locked(fl.ex, s, now)
                    if self._retire(arr, fl.parts):
                        self._cv.notify_all()
                    retire.__exit__(None, None, None)
                    self._pump_retiring = None
                    self._busy[s] -= 1
                    if self._all_idle():
                        self._idle.notify_all()

    # -- hedged retire (speculative duplicate launches) -------------------------------
    @staticmethod
    def _buf_ready(buf) -> bool:
        """Non-blocking launch-buffer readiness (jax Arrays expose
        ``is_ready``; anything else is host data, ready by definition)."""
        r = getattr(buf, "is_ready", None)
        return True if r is None else bool(r())

    def _await_flight(self, s: int, fl: _Flight, seq: int):
        """Block (outside the lock) until one of the flight's buffers is
        ready; returns ``(host array, winning executor, round-trip
        seconds, won_by_hedge)``.

        Fast path — no injected stall and hedging not armed — is the
        plain blocking ``np.asarray`` the pre-hedge pump did, in the
        ``pump.fetch`` span of launch ``seq``. Hedging
        arms only when the policy allows it, the shard has more than one
        stream, and its straggler detector is past warmup (an untrained
        EWMA would hedge compile time); the cutoff is
        :meth:`StragglerDetector.hedge_cutoff`. Once the wait crosses
        it, ONE duplicate launch of the same group is dispatched on a
        different healthy stream and both buffers race — first ready
        resolves the tickets, the loser is dropped unread (its buffer
        dies with the flight; nothing double-counts)."""
        det = self._stragglers[s]
        p = self._policy
        can_hedge = (p.hedge and self._sharded_ex is not None
                     and det.n > det.warmup
                     and self._sharded_ex.n_streams(s) > 1)
        if not can_hedge and fl.ready_at == 0.0:
            with span("pump.fetch", seq=seq) as fetch:
                arr = np.asarray(fl.dev)      # blocks on device, unlocked
                fetch.set_metadata(nbytes=arr.nbytes)
            return arr, fl.ex, time.perf_counter() - fl.t0, False
        cutoff = det.hedge_cutoff(p.hedge_factor, p.hedge_min_s)
        while True:
            now = time.perf_counter()
            if fl.hedge_dev is not None and now >= fl.hedge_ready_at \
                    and self._buf_ready(fl.hedge_dev):
                arr = np.asarray(fl.hedge_dev)
                return arr, fl.hedge_ex, now - fl.hedge_t0, True
            if now >= fl.ready_at and self._buf_ready(fl.dev):
                arr = np.asarray(fl.dev)
                return arr, fl.ex, now - fl.t0, False
            if can_hedge and not fl.hedge_done \
                    and now - fl.t0 >= cutoff:
                self._try_hedge(s, fl)
            time.sleep(2e-4)

    def _try_hedge(self, s: int, fl: _Flight) -> None:
        """Dispatch ONE speculative duplicate of the flight's group on a
        different healthy stream (pump thread, lock taken briefly for
        stream selection). At most one attempt per flight; a duplicate
        that fails to launch strikes ITS stream's breaker and the
        primary wait continues — hedging never makes an outcome worse.
        The duplicate's buffer layout matches ``fl.parts`` (same group,
        same buckets), so the retire path needs no translation."""
        fl.hedge_done = True
        avoid = frozenset({fl.ex.stream_token}) | fl.group[0].avoid
        with self._lock:
            now = time.perf_counter()
            alts = [e for e in self._healthy_streams(s, now)
                    if e.stream_token not in avoid]
            if not alts:
                return                    # nowhere healthy to hedge to
            ex2, st2 = self._pick_stream(s, avoid)
            if ex2.stream_token == fl.ex.stream_token:
                return
        t1 = time.perf_counter()
        try:
            dev2, _parts2, _nb2, stall2 = self._launch(fl.group, s,
                                                       ex2, st2)
        except Exception:
            with self._lock:
                self._strike_locked(ex2, s, time.perf_counter())
            return
        fl.hedge_ex = ex2
        fl.hedge_t0 = t1
        fl.hedge_ready_at = t1 + stall2 if stall2 else 0.0
        fl.hedge_dev = dev2
        with self._lock:
            self.stats["hedges"] += 1

    def _take_group(self, queue: deque, now: float) -> list[_Chunk]:
        """Pop one launch group: the :meth:`_select_class` winner's
        chunks, up to the CLASS's coalesce depth, sharing the class
        head's bucket shape (FIFO preserved within the class; other
        classes' chunks are skipped in place). Stops scanning once the
        group is full and splices the tail back in bulk, so a long
        queued burst costs O(Q) per tick, not O(Q) per chunk.

        The eviction point for dead work (lock held): chunks of already-
        failed tickets are dropped on sight, a chunk whose ticket's
        ``deadline_ms`` expired resolves it to :class:`DeadlineExceeded`
        and is dropped BEFORE launch, and the take stops at a selected-
        class chunk still in retry backoff (``not_before`` ahead of
        ``now``) — so the group may come back empty.

        Each chunk taken records its queue wait, ``now`` minus its
        enqueue time, in its class's ``queue`` histogram."""
        klass, _head, _hold = self._select_class(queue, now)
        if klass is None:
            return []
        rc = self._classes[klass]
        cap = self._coalesce_for(rc)
        group: list[_Chunk] = []
        rest: deque[_Chunk] = deque()
        bucket = None
        while queue:
            ch = queue[0]
            if ch.ticket in self._dead:
                queue.popleft()
                continue
            dl = self._deadlines.get(ch.ticket)
            if dl is not None and now > dl:
                queue.popleft()
                self._fail_ticket_locked(ch.ticket, DeadlineExceeded(
                    f"ticket {ch.ticket} missed its deadline before launch",
                    ticket=ch.ticket, shard=ch.shard), timeout=True)
                continue
            if len(group) >= cap:
                break
            if ch.klass != klass:
                rest.append(queue.popleft())
                continue
            if ch.not_before > now:
                break
            queue.popleft()
            if bucket is None:
                bucket = ch.bucket
            (group if ch.bucket == bucket else rest).append(ch)
        rest.extend(queue)
        queue.clear()
        queue.extend(rest)
        waits = self._class_stats[klass]["queue"]
        for ch in group:
            waits.record(now - ch.t_enq)
        return group

    def _launch(self, group: list[_Chunk], s: int, ex, stream: int):
        """Dispatch ONE launch for a coalesced group on ``ex`` — the
        shard-``s`` stream :meth:`_pick_stream` chose (pump thread only).

        Packed plans: a flat (lanes * bucket,) int32 SHARD-LOCAL index
        vector — padded to the group's class width (:meth:`_lanes`) so
        every launch of a class shares one compiled shape per bucket —
        into the shard executor's indexed gather; host->device traffic is
        the indices alone. A hedged duplicate of the same group gets the
        same width, so its parts' row offsets hold. int32 plans:
        the classic stacked code slice for a single chunk. Either way the
        launch buffer is a flat (rows, F) array and each part records its
        chunk's row offset into it.

        The chaos hook fires first, BEFORE any dispatch: an injected fault
        or delay lands exactly where a real device error would, so it
        exercises the same recovery path. The hook's return value is the
        launch's injected STALL (simulated slow device compute) — passed
        through as the last element of the return tuple so the pump can
        gate the flight's retire readiness on it.
        """
        stall = 0.0
        if self._faults is not None:
            stall = self._faults.before_launch(s, stream,
                                               device=ex.device,
                                               klass=group[0].klass)
        bucket = group[0].bucket
        if self.packed:
            mat = np.empty((self._lanes(group), bucket), np.int32)
            for i, ch in enumerate(group):
                mat[i] = pad_rows_edge(ch.rows, bucket)
            mat[len(group):] = mat[len(group) - 1]   # surplus lanes unread
            dev = ex._rows_future(mat.reshape(-1))
            parts = [(ch.ticket, ch.n, ch.dest, i * bucket)
                     for i, ch in enumerate(group)]
            return dev, parts, mat.nbytes, stall
        ch = group[0]
        codes = self._slice_padded(ch.rows, bucket)
        # np codes go straight into the jit'd gather — its argument
        # transfer is the one host->device code shipment
        dev = ex.gather_device(codes)
        return dev, [(ch.ticket, ch.n, ch.dest, 0)], int(codes.nbytes), \
            stall

    def _retire(self, arr: np.ndarray, parts: list) -> bool:
        """Distribute one retired launch buffer to its tickets (lock held);
        True if any ticket completed (its waiters need a wake).

        Single-chunk requests take the sliced piece directly (copied when
        small, so the result doesn't pin the whole coalesced launch buffer
        for its lifetime); multi-chunk requests assemble into a preallocated
        per-ticket (rows, F) buffer via each chunk's destination map — the
        request-order concatenation for routed/sharded splits.

        ``self._retire_prog`` journals how many leading parts are fully
        distributed (bumped as each part's bookkeeping completes): the
        pump supervisor re-enqueues exactly the rest of a crashed
        retire's group. Callers reset it to 0 per launch buffer.
        """
        landed = False
        for i in range(self._retire_prog, len(parts)):
            ticket, n, dest, off = parts[i]
            total = self._chunks_total.get(ticket)
            if total is None:
                # dropped by shutdown(drain=False)
                self._ticket_class.pop(ticket, None)
                self._retire_prog = i + 1
                continue
            piece = arr[off:off + n]
            if total == 1:
                # copy only when the piece is a SLIVER of the coalesced
                # launch buffer (a view would pin the whole (lanes*bucket,
                # F) array for the result's lifetime); a full group's lanes
                # collectively own the buffer anyway, and the copies are
                # GIL-held pump time — 8x bounds the pinning overhead
                if piece.size * 8 < arr.size:
                    piece = piece.copy()
                self._results[ticket] = piece
            else:
                buf = self._out_buf.get(ticket)
                if buf is None:
                    # width read at allocation time, NOT cached at
                    # construction, so a refresh() that grows a dictionary
                    # (wider out_dim) keeps the service serving. Refresh is
                    # not atomic w.r.t. IN-FLIGHT requests — a ticket whose
                    # chunks straddle a widening refresh would mix widths
                    # whatever the buffer shape (the pre-mesh concatenate
                    # had the same contract): drain() before refreshing
                    buf = np.empty((self._ticket_rows[ticket],
                                    self.plan.out_dim), arr.dtype)
                    self._out_buf[ticket] = buf
                if isinstance(dest, np.ndarray):
                    buf[dest] = piece
                else:
                    buf[dest:dest + n] = piece
                done = self._chunks_done.get(ticket, 0) + 1
                if done < total:
                    self._chunks_done[ticket] = done
                    self._retire_prog = i + 1
                    continue
                self._chunks_done.pop(ticket, None)
                self._results[ticket] = self._out_buf.pop(ticket)
            del self._chunks_total[ticket]
            self._ticket_rows.pop(ticket, None)
            self._deadlines.pop(ticket, None)
            landed = True
            t0 = self._submitted_at.pop(ticket, None)
            if t0 is not None:
                lat = time.perf_counter() - t0
                self.stats["latency_s_total"] += lat
                self.stats["completed"] += 1
                self.stats["latency_samples_total"] += 1
                self._lat_hist.record(lat)
                cs = self._class_stats.get(
                    self._ticket_class.pop(ticket, "default"))
                if cs is not None:
                    cs["completed"] += 1
                    cs["hist"].record(lat)
            self._retire_prog = i + 1
        return landed

    # -- adaptive shard management ---------------------------------------------------
    def _drain_admin(self) -> None:
        """Run queued shard-set mutations (lock held, pump thread only)."""
        while self._admin_q:
            fn, ev, box = self._admin_q.popleft()
            try:
                box.append(fn())
            except BaseException as e:
                box.append(e)
            ev.set()

    def _fail_admin(self, err: BaseException) -> None:
        """Unblock admin waiters when the pump dies (lock held)."""
        while self._admin_q:
            _, ev, box = self._admin_q.popleft()
            box.append(err)
            ev.set()

    def _run_admin(self, fn):
        """Execute ``fn`` under the lock ON THE PUMP THREAD and return its
        result. The pump is the only thread that dispatches launches, so
        marshalling every shard-set mutation onto it makes mutation-vs-
        launch races impossible by construction; a mutation requested from
        the pump itself (the auto monitor) just runs inline."""
        if threading.current_thread() is self._pump:
            return fn()
        ev = threading.Event()
        box: list = []
        with self._lock:
            self._check_pump()
            if self._shutdown:
                raise RuntimeError("service is shut down")
            self._admin_q.append((fn, ev, box))
            self._work.notify_all()
        while not ev.wait(timeout=0.5):
            with self._lock:
                self._check_pump()
        if isinstance(box[0], BaseException):
            raise box[0]
        return box[0]

    def _require_mesh(self) -> None:
        if self._sharded_ex is None:
            raise RuntimeError("adaptive shard management needs a "
                               "sharded=True service over a packed plan")

    def _add_replica_locked(self, shard: int, device=None,
                            avoid: frozenset = frozenset()):
        """The ONE replica-add bookkeeping path (lock held, pump thread) —
        shared by the public mutator and the monitor policies so stats and
        wake discipline can never drift apart. ``avoid`` (device ids) keeps
        the failover policy from re-replicating ONTO a device whose stream
        breaker is open."""
        # never place on a DEAD device, whatever the caller avoids
        avoid = frozenset(avoid) | frozenset(self._device_health.down)
        ex = self._sharded_ex.add_replica(shard, device, avoid=avoid)
        self.stats["replicas_added"] += 1
        self._work.notify_all()         # the shard's window just widened
        return ex.device

    def _drop_replica_locked(self, shard: int):
        ex = self._sharded_ex.drop_replica(shard)
        self._discard_breaker_locked(ex)
        self.stats["replicas_dropped"] += 1
        return ex.device

    def add_replica(self, shard: int, device=None):
        """Replicate ``shard``'s resident word stream to ``device`` (default:
        the least-loaded serve device not already holding a copy) and fan
        reads out across the copies. Returns the replica's device. An
        explicitly configured ``max_replicas`` bounds this too (the
        monitor's device-count default applies only to the auto policy —
        an operator's explicit call may replicate on a single device)."""
        self._require_mesh()

        def op():
            if self.max_replicas is not None and \
                    len(self._sharded_ex.replicas[shard]) >= self.max_replicas:
                raise ValueError(f"shard {shard} already has "
                                 f"max_replicas={self.max_replicas} replicas")
            return self._add_replica_locked(shard, device)
        return self._run_admin(op)

    def drop_replica(self, shard: int):
        """Retire one replica of ``shard`` (in-flight launches finish; the
        routing change is immediate). Returns the dropped device."""
        self._require_mesh()
        return self._run_admin(lambda: self._drop_replica_locked(shard))

    def split_tail(self, cut: int | None = None, device=None) -> int:
        """Split the open tail shard at parent row ``cut`` (default: its
        word-aligned midpoint) and swap the routing table atomically —
        queued chunks of the old tail are re-routed (split in two when they
        straddle the cut) with their tickets, order, and linger deadlines
        intact. Returns the new shard's index."""
        self._require_mesh()
        return self._run_admin(lambda: self._apply_split_locked(cut, device))

    def rebalance(self) -> dict:
        """Run the load monitor's policy decisions NOW (on the pump thread)
        and return the actions taken: ``{'split': [(old, new, cut)],
        'replicated': [(shard, device)], 'dropped': [(shard, device)],
        'failover_replicated': [(shard, device)],
        'rebuilt': [(shard, device)]}``. Safe (a no-op) on unsharded
        services."""
        return self._run_admin(self._rebalance_locked)

    def _unhealthy_devices(self, now: float) -> set[int]:
        """Device ids currently behind an OPEN stream breaker (lock held)
        — placement to avoid when re-replicating for failover."""
        thr = self._policy.breaker_fails
        bad: set[int] = set(self._device_health.down)
        for s in range(self._n_shards):
            for ex in self._shard_streams(s):
                if self._breaker(ex).is_open(thr, now):
                    bad.add(id(ex.device))
        return bad

    def _rebalance_locked(self) -> dict:
        """Monitor tick (lock held, pump thread): update the per-shard
        request-rate EWMA from the ``shard_batches`` stats deltas, then
        apply the adaptive policies — split the tail shard past its row
        budget, replicate the hottest shard / shed replicas of cooled
        ones, and re-replicate shards whose streams went unhealthy
        (failover), and — first of all — emergency-rebuild shards that
        device loss left with no live stream. One action of each kind
        per tick keeps rebalancing incremental (the next tick
        re-evaluates against the moved load)."""
        actions: dict = {"split": [], "replicated": [], "dropped": [],
                         "failover_replicated": [], "rebuilt": [],
                         "demoted": [], "promoted": []}
        sx = self._sharded_ex
        if sx is None:
            return actions
        self.stats["rebalances"] += 1
        # host-gather groups count as monitor work too: a miss-heavy
        # workload (everything off-device) must still tick, or nothing
        # would ever promote
        self._mon_mark = self.stats["launches"] + self.stats["host_gathers"]
        sb = self.stats["shard_batches"]
        a = self._mon_alpha
        for s in range(len(sb)):
            # launched batches + host-served chunks: a warm/cold shard's
            # misses never bump shard_batches, but they ARE load — the
            # promotion ladder orders by exactly this heat
            total = sb[s] + self._host_served[s]
            delta = total - self._mon_last[s]
            self._mon_last[s] = total
            self._mon_ewma[s] = a * delta + (1 - a) * self._mon_ewma[s]
        # -- policy 1: tail re-shard under streaming growth --
        if self.row_budget is not None and sx.tail_rows() > self.row_budget:
            old = len(sx.shards) - 1
            start, _ = sx.shards[old].shard_bounds
            cut = start + max(32, self.row_budget // 32 * 32)
            new = self._apply_split_locked(cut)
            actions["split"].append((old, new, cut))
        # -- policy 4: emergency rebuild of shards with zero live streams --
        # a shard orphaned by device loss must get a fresh stream before
        # normal serving resumes (host gathers cover it meanwhile); runs
        # before the replication policies so they see the rebuilt set
        for s in sorted(set(self._needs_rebuild)):
            if self._rebuild_shard_locked(s):
                actions["rebuilt"].append((s, sx.devices[s]))
        now = time.perf_counter()
        sick = {s for s in range(self._n_shards)
                if len(self._healthy_streams(s, now))
                < len(self._shard_streams(s))}
        cap = self.max_replicas
        if cap is None:
            cap = len({id(d) for d in sx.device_pool}) - 1
        # -- policy 2: hot-shard replication / cold-shard shedding --
        ewma = self._mon_ewma
        mean = sum(ewma) / max(len(ewma), 1)
        if mean > 0 and len(ewma) > 1:
            # an orphaned (rebuild-pending) or off-device (warm/cold)
            # shard is host-served — its load picture is a PROMOTION
            # signal, not a replication one
            hot = max((s for s in range(len(ewma))
                       if s not in self._needs_rebuild
                       and s not in self._offdevice),
                      key=lambda s: ewma[s], default=None)
            # hot = hot_factor x the mean of the OTHER shards — including
            # the hot shard in the reference would make the threshold
            # unreachable whenever hot_factor >= n_shards (a 4-shard mesh
            # under 100% skew never exceeds 4x its own all-shard mean)
            if hot is not None:
                others = (sum(ewma) - ewma[hot]) / (len(ewma) - 1)
                if ewma[hot] > self.hot_factor * others \
                        and len(sx.replicas[hot]) < cap:
                    # a replica is stream bytes too: route placement
                    # around devices without budget headroom, and skip
                    # the action entirely when nowhere fits
                    bavoid = self._budget_avoid_locked(
                        sx.executors[hot].stream_nbytes())
                    if any(id(d) not in bavoid for d in sx.device_pool):
                        actions["replicated"].append(
                            (hot, self._add_replica_locked(
                                hot, avoid=bavoid)))
            for s in range(len(ewma)):
                # never shed a replica of a shard with an unhealthy
                # stream — the copies are its availability margin
                if s != hot and sx.replicas[s] and ewma[s] < mean \
                        and s not in sick:
                    actions["dropped"].append(
                        (s, self._drop_replica_locked(s)))
                    break
        # -- policy 3: failover re-replication around unhealthy streams --
        # a shard with an open breaker and < 2 healthy copies gets a fresh
        # replica on a device that is NOT itself behind an open breaker, so
        # retries have somewhere healthy to fail over to while the sick
        # stream rides out its cooldown
        if sick:
            bad = self._unhealthy_devices(now)
            for s in sorted(sick):
                # rebuild-pending shards are policy 4's problem — a
                # replica would not make host-serving any healthier;
                # off-device shards host-serve by design (stale breaker
                # state from before their demotion is not a failover
                # signal either)
                if s in self._needs_rebuild or s in self._offdevice:
                    continue
                if len(self._healthy_streams(s, now)) < 2 \
                        and len(sx.replicas[s]) < cap:
                    avoid = bad | self._budget_avoid_locked(
                        sx.executors[s].stream_nbytes())
                    actions["failover_replicated"].append(
                        (s, self._add_replica_locked(s, avoid=avoid)))
        # -- policies 5-7: the tiered-residency ladder --
        self._tier_policy_locked(actions)
        return actions

    def _apply_split_locked(self, cut: int | None = None,
                            device=None) -> int:
        """Tail split + atomic routing-table swap (lock held, pump thread).

        Executor-level swap first (new shard plan/stream committed, bisect
        bounds flipped, old tail closed), then the service side: one new
        launch queue / in-flight window / stats lane APPENDED (existing
        shard indices never move — stats continuity), old-tail queued
        chunks re-routed to whichever side of the cut owns their rows, and
        the route generation bumped so any submit that raced the swap
        rebuilds its chunks instead of enqueueing against stale bounds.
        """
        self._require_mesh()
        sx = self._sharded_ex
        old = len(sx.shards) - 1
        new = sx.split_tail(cut=cut, device=device)
        self._queues.append(deque())
        self._inflights.append(deque())
        self._busy.append(0)
        for k in ("shard_launches", "shard_batches", "shard_bytes_h2d"):
            self.stats[k].append(0)
        self._mon_ewma.append(0.0)
        self._mon_last.append(0)
        self._stream_rr.append(0)
        self._stragglers.append(self._new_straggler())
        # the fresh tail commits hot (splits happen on the open, appending
        # shard — always device-resident); if that overflows the device
        # budget the next tier-policy tick demotes the coldest resident
        self._tier.append("hot")
        self.stats["tier_hot"] += 1
        self._warm_ticks.append(0)
        self._host_served.append(0)
        self._n_shards += 1
        self.stats["shard_splits"] += 1
        self._reroute_after_split(old, new)
        self._route_gen += 1
        self._work.notify_all()         # the new queue may be launchable
        return new

    def _reroute_after_split(self, old: int, new: int) -> None:
        """Move queued old-tail chunks whose rows now belong to the new
        shard (lock held). A chunk straddling the cut splits into two —
        its ticket's chunk count grows by one, each piece keeps its output
        destinations, so the request retires complete and in order."""
        sx = self._sharded_ex
        cut_local = int(sx.shards[new]._start - sx.shards[old]._start)
        q = self._queues[old]
        if not q:
            return
        keep: deque = deque()
        moved: deque = deque()
        for ch in q:
            below = ch.rows < cut_local
            if below.all():
                keep.append(ch)
                continue
            if not below.any():
                ch.rows = ch.rows - cut_local
                ch.shard = new
                moved.append(ch)
                continue
            pos = (ch.dest + np.arange(ch.n)
                   if isinstance(ch.dest, (int, np.integer)) else ch.dest)
            ra, rb = ch.rows[below], ch.rows[~below] - cut_local
            ka = _Chunk(ch.ticket, ra, ra.shape[0],
                        self._bucket(ra.shape[0]), old, pos[below],
                        ch.t_enq, klass=ch.klass)
            kb = _Chunk(ch.ticket, rb, rb.shape[0],
                        self._bucket(rb.shape[0]), new, pos[~below],
                        ch.t_enq, klass=ch.klass)
            keep.append(ka)
            moved.append(kb)
            self._chunks_total[ch.ticket] += 1
            # keep the submit-time accounting honest: the two pieces pad
            # (and range-classify) differently than the chunk they replace
            self.stats["padded_rows"] += (ka.bucket - ka.n) + \
                (kb.bucket - kb.n) - (ch.bucket - ch.n)
            self.stats["packed_ranges"] += (
                int(self._aligned_range(ka.rows)) +
                int(self._aligned_range(kb.rows)) -
                int(self._aligned_range(ch.rows)))
        q.clear()
        q.extend(keep)
        self._queues[new].extend(moved)

    # -- tiered residency (HBM-hot / host-warm / RLE-cold ladder) ---------------------
    def _set_tier_locked(self, s: int, tier: str) -> None:
        """Flip one shard's tier label + the gauge stats + the off-device
        routing set (lock held). The ONE place tier state changes, so the
        gauges can never drift from the labels."""
        old = self._tier[s]
        if old == tier:
            return
        self.stats["tier_" + old] -= 1
        self.stats["tier_" + tier] += 1
        self._tier[s] = tier
        if tier == "hot":
            self._offdevice.discard(s)
        else:
            self._offdevice.add(s)

    def _budget_avoid_locked(self, need: int) -> frozenset:
        """Device ids WITHOUT headroom for ``need`` more stream bytes
        (empty when uncapped) — the placement-avoid set replica adds pass
        so read fan-out respects the same budget residency does."""
        sx = self._sharded_ex
        if sx is None or sx.hbm_budget_bytes is None:
            return frozenset()
        ledger = sx.budget_ledger()
        return frozenset(id(d) for d in sx.device_pool
                         if not ledger.fits(id(d), need))

    def _demote_shard_locked(self, s: int, tier: str = "warm") -> int:
        """Move shard ``s`` down the ladder (lock held, pump thread).
        Returns the device bytes freed.

        ``warm``: every replica is dropped and the primary's resident
        words are dereferenced (in-flight launches finish — they hold
        their operands; the buffer frees when the last reference drops).
        ``cold``: additionally the host packed copy compresses to RLE
        runs (:meth:`_PackedShardPlan.demote_cold`) — misses then decode
        runs on the fly, still bit-exact. The open tail shard cannot go
        cold (its row range is still growing under appends); demote it
        to warm or :meth:`split_tail` first. Queued and future requests
        for the shard serve through the host path the moment the tier
        flips (:meth:`_pick_action` routes off-device shards to
        hostserve before considering launches)."""
        sx = self._sharded_ex
        sp = sx.shards[s]
        if tier == "cold" and sp._last:
            raise ValueError("the open tail shard cannot go cold (its RLE "
                             "runs would close a still-appending range); "
                             "demote to 'warm' or split_tail() first")
        if self._tier[s] == "cold" and tier == "warm":
            # UP-ladder within the host tiers: restore the packed copy,
            # drop the runs — not a demotion, nothing device-side changes
            if sp.is_cold:
                sp.rehydrate()
                self.stats["rehydrations"] += 1
            self._set_tier_locked(s, "warm")
            return 0
        if self._tier[s] == tier:
            return 0
        while sx.replicas[s]:
            self._drop_replica_locked(s)
        freed = sx.executors[s].evict_words()
        if tier == "cold" and not sp.is_cold:
            sp.demote_cold()
        self._set_tier_locked(s, tier)
        self._warm_ticks[s] = 0
        # a demoted shard host-serves by DESIGN — it no longer needs the
        # emergency rebuild a device loss may have queued for it
        self._needs_rebuild.discard(s)
        self.stats["demotions"] += 1
        return freed

    def _promote_shard_locked(self, s: int) -> bool:
        """Re-commit shard ``s``'s resident word stream (lock held, pump
        thread) — the UP move of the ladder. Cold shards rehydrate their
        host packed copy from the RLE runs first; the device commit is
        the same version-keyed put a refresh uses, and when the shard's
        home device died it rebuilds on a survivor instead
        (:meth:`ShardedFeatureExecutor.rebuild_on`). False when no device
        survives — the shard stays host-served (a cold one has still
        moved up to warm: its packed copy is back)."""
        sx = self._sharded_ex
        if self._tier[s] == "hot":
            return True
        sp = sx.shards[s]
        if sp.is_cold:
            sp.rehydrate()
            self.stats["rehydrations"] += 1
            if self._tier[s] == "cold":
                self._set_tier_locked(s, "warm")
        ex = sx.executors[s]
        down = set(self._device_health.down)
        if ex.device is not None and id(ex.device) in down:
            try:
                sx.rebuild_on(s, lost=down)
            except ValueError:
                return False        # no surviving device — stay host-served
            self._discard_breaker_locked(ex)
        else:
            ex.ensure_range_capacity(sp.n_rows)
        self._set_tier_locked(s, "hot")
        self._warm_ticks[s] = 0
        self._promote_pending.discard(s)
        self.stats["promotions"] += 1
        self._work.notify_all()     # the shard's queue is launchable again
        return True

    def _try_promote_locked(self, s: int) -> bool:
        """Budget-respecting promotion (lock held, pump thread): displace
        COLDER resident shards (strictly lower EWMA — equal-heat shards
        never thrash) off the target device until ``s`` fits, then
        promote. False when the stream can never fit, nothing colder can
        be displaced, or no device survives."""
        sx = self._sharded_ex
        if sx is None or s in self._needs_rebuild:
            return False
        if self._tier[s] == "hot":
            return True                   # idempotent (a free-beat promote
                                          # may have beaten this call)
        budget = sx.hbm_budget_bytes
        if budget is not None:
            ex = sx.executors[s]
            need = ex.stream_nbytes()
            if need > budget:
                return False              # a stream that can NEVER fit
            dev_id = id(ex.device) if ex.device is not None else None
            if dev_id is not None and dev_id in self._device_health.down:
                # the promote will rebuild on the least-loaded survivor;
                # post-promotion enforcement settles any overshoot there
                dev_id = None
            guard = 0
            while dev_id is not None \
                    and not sx.budget_ledger().fits(dev_id, need):
                victims = [v for v in range(self._n_shards)
                           if v != s and self._tier[v] == "hot"
                           and self._mon_ewma[v] < self._mon_ewma[s]
                           and any(id(e.device) == dev_id
                                   and e.resident_bytes() > 0
                                   for e in sx.stream_executors(v))]
                guard += 1
                if not victims or guard > self._n_shards:
                    return False          # nothing colder to displace
                self._demote_shard_locked(
                    min(victims, key=lambda v: self._mon_ewma[v]), "warm")
        ok = self._promote_shard_locked(s)
        if ok and budget is not None:
            self._enforce_budget_locked()
        return ok

    def _enforce_budget_locked(self, actions: dict | None = None) -> None:
        """Settle every device back under the byte budget (lock held):
        demote the coldest (min-EWMA) hot shard holding a stream on an
        over-budget device, repeat until under. Ground truth comes from
        :meth:`ShardedFeatureExecutor.device_bytes` (live buffers, never
        a ledger), so transients from splits, rebuilds and replica adds
        all settle here."""
        sx = self._sharded_ex
        if sx is None or sx.hbm_budget_bytes is None:
            return
        budget = sx.hbm_budget_bytes
        for _ in range(4 * self._n_shards + 8):
            over = {d: b for d, b in sx.device_bytes().items() if b > budget}
            if not over:
                return
            dev_id = next(iter(over))
            victims = [v for v in range(self._n_shards)
                       if self._tier[v] == "hot"
                       and any(id(e.device) == dev_id
                               and e.resident_bytes() > 0
                               for e in sx.stream_executors(v))]
            if not victims:
                return
            v = min(victims, key=lambda x: self._mon_ewma[x])
            self._demote_shard_locked(v, "warm")
            if actions is not None:
                actions["demoted"].append((v, "warm"))

    def _tier_policy_locked(self, actions: dict) -> None:
        """The monitor's residency policies (lock held, pump thread), run
        at the end of every rebalance tick:

        - **budget enforcement** — settle over-budget devices (coldest
          resident demotes to warm);
        - **cold aging** — a warm, closed, non-rebuilding shard quiet for
          ``cold_after`` consecutive ticks compresses to RLE runs (the
          host packed copy is the next-biggest residency after HBM);
        - **promotion** — the hottest off-device shard with real load
          moves up, displacing colder residents under the budget (misses
          also promote sooner through the pump's free-beat promote arm —
          this tick-side policy catches load the beat missed)."""
        sx = self._sharded_ex
        if sx is None:
            return
        self._enforce_budget_locked(actions)
        for s in range(self._n_shards):
            if self._tier[s] != "warm" or s in self._needs_rebuild \
                    or sx.shards[s]._last:
                continue
            self._warm_ticks[s] += 1
            if self._warm_ticks[s] >= self.cold_after:
                self._demote_shard_locked(s, "cold")
                actions["demoted"].append((s, "cold"))
        cand = [s for s in self._offdevice
                if s not in self._needs_rebuild and self._mon_ewma[s] > 0]
        if cand:
            s = max(cand, key=lambda i: self._mon_ewma[i])
            if self._try_promote_locked(s):
                actions["promoted"].append(s)

    @property
    def tiers(self) -> list[str]:
        """Residency tier per shard: 'hot' / 'warm' / 'cold'."""
        with self._lock:
            return list(self._tier)

    def device_bytes(self) -> dict[int, int]:
        """LIVE resident word-stream bytes per device (``id(device)``
        keyed) — what the budget is enforced against. Empty for
        unsharded services."""
        with self._lock:
            return ({} if self._sharded_ex is None
                    else self._sharded_ex.device_bytes())

    def demote(self, shard: int, tier: str = "warm") -> int:
        """Manually move ``shard`` down the ladder ('warm' frees its
        device words, 'cold' additionally compresses the host copy to RLE
        runs). Runs on the pump like every shard-set mutation; returns
        the device bytes freed. Requests keep serving bit-exact through
        the host path throughout."""
        if tier not in ("warm", "cold"):
            raise ValueError(f"tier must be 'warm' or 'cold', got {tier!r}")
        self._require_mesh()
        return self._run_admin(lambda: self._demote_shard_locked(shard, tier))

    def promote(self, shard: int) -> bool:
        """Manually promote ``shard`` to the hot tier (budget-respecting:
        colder residents are displaced to warm when the device is full).
        Returns False when it cannot fit or no device survives — the
        shard keeps host-serving."""
        self._require_mesh()
        return self._run_admin(lambda: self._try_promote_locked(shard))

    # -- result retrieval ----------------------------------------------------------
    def poll(self, ticket: int) -> bool:
        """True once the ticket has RESOLVED — its result is on host, or it
        failed and :meth:`result` will raise its typed error. Non-blocking
        and dispatch-free: the pumps own all launching/retiring. Raises
        KeyError for unknown/already-collected tickets (like ``result``) so
        a poll loop can't spin forever on a bad ticket."""
        with self._lock:
            self._check_pump()
            if ticket in self._results or ticket in self._errors:
                return True
            if ticket not in self._chunks_total:
                raise KeyError(f"unknown or already-collected ticket {ticket}")
            return False

    def _queued_while_paused(self, ticket: int | None) -> bool:
        """True when blocking on this work would deadlock: the pumps are
        paused (and not shutting down, which overrides pause) and the
        awaited chunks are still queued — nothing will ever launch them
        until ``resume()``. Lock held."""
        if not self._paused or self._shutdown:
            return False
        if ticket is None:
            return any(self._queues)
        return any(ch.ticket == ticket for q in self._queues for ch in q)

    def result(self, ticket: int,
               timeout: float | None = None) -> np.ndarray:
        """Block until the ticket RESOLVES: return its features, or raise
        its typed error (:class:`ServeError`; :class:`DeadlineExceeded`
        when its ``deadline_ms`` expired — both consumed, like a result).

        Purely a wait: the pumps launch and retire; this just sleeps on
        the service condition until the ticket lands (or is unknown).
        ``timeout`` (seconds) bounds the wait itself — a builtin
        ``TimeoutError`` is raised when it elapses, and the ticket stays
        pending and retrievable. Raises RuntimeError instead of
        deadlocking if the service is paused with this ticket's chunks
        still unlaunched.
        """
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._lock:
            # claim the ticket so a concurrent drain() can't sweep it away
            # between a pump landing it and this thread waking up
            self._claimed.add(ticket)
            try:
                while True:
                    self._check_pump()
                    if ticket in self._results:
                        return self._results.pop(ticket)
                    err = self._errors.pop(ticket, None)
                    if err is not None:
                        raise err
                    if ticket not in self._chunks_total:
                        raise KeyError(
                            f"unknown or already-collected ticket {ticket}")
                    if self._queued_while_paused(ticket):
                        raise RuntimeError(
                            f"ticket {ticket} is queued but the service is "
                            "paused — resume() before blocking on results")
                    wait = 0.5
                    if deadline is not None:
                        left = deadline - time.perf_counter()
                        if left <= 0:
                            raise TimeoutError(
                                f"result({ticket}) timed out after "
                                f"{timeout} s")
                        wait = min(wait, left)
                    self._cv.wait(timeout=wait)
            finally:
                self._claimed.discard(ticket)

    def drain(self, timeout: float | None = None) -> dict[int, np.ndarray]:
        """Wait for every pump to finish everything queued/in flight;
        return {ticket: features} collected — except tickets another thread
        is blocked on in result(), which stay theirs. Tickets that FAILED
        are not in the dict — their typed errors stay retrievable via
        :meth:`result`/:meth:`collect`. ``timeout`` (seconds) bounds the
        wait with a builtin ``TimeoutError`` (nothing is collected then).
        Raises RuntimeError instead of deadlocking if called while paused
        with chunks queued."""
        deadline = None if timeout is None \
            else time.perf_counter() + timeout
        with self._lock:
            try:
                # a drain wants everything NOW: partial groups stop
                # lingering while ANY drain is in progress (a counter, so
                # one drain finishing cannot un-flush a concurrent one)
                self._flushes += 1
                self._work.notify_all()
                while not self._all_idle():
                    self._check_pump()
                    if self._queued_while_paused(None):
                        raise RuntimeError("queue is held by pause() — "
                                           "resume() before drain()")
                    wait = 0.5
                    if deadline is not None:
                        left = deadline - time.perf_counter()
                        if left <= 0:
                            raise TimeoutError(
                                f"drain() timed out after {timeout} s")
                        wait = min(wait, left)
                    self._idle.wait(timeout=wait)
                self._check_pump()
            finally:
                self._flushes -= 1
            out = {t: r for t, r in self._results.items()
                   if t not in self._claimed}
            for t in out:
                del self._results[t]
            return out

    def collect(self, timeout: float | None = None) -> dict:
        """Drain, then return EVERYTHING that resolved: ``{ticket:
        features | ServeError}`` — completed tickets map to their arrays,
        failed ones to their typed errors (both consumed, retrieved once).
        The 'give me all outcomes, including what broke' retrieval a
        caller uses after a faulty period; check each value with
        ``isinstance(v, Exception)``. ``timeout`` as in :meth:`drain`."""
        out: dict = dict(self.drain(timeout))
        with self._lock:
            errs = {t: e for t, e in self._errors.items()
                    if t not in self._claimed}
            for t in errs:
                del self._errors[t]
        out.update(errs)
        return out

    # -- predicate pushdown queries (no pump involvement) -----------------------------
    def _pushdown_ex(self):
        if not self.packed:
            raise RuntimeError("predicate pushdown needs a packed plan "
                               "(resident word streams)")
        return self._sharded_ex if self._sharded_ex is not None \
            else self._executor

    def filtered_rows(self, where) -> np.ndarray:
        """Matching row indices via the device predicate scan (per shard on
        a mesh service, matches found where the data lives)."""
        return self._pushdown_ex().filtered_rows(where)

    def count_where(self, where) -> int:
        """SELECT COUNT(*) WHERE — one device scan + reduction per shard."""
        return self._pushdown_ex().count_where(where)

    def groupby_where(self, column: str, where):
        """GROUP BY column COUNT(*) WHERE — masked device histograms."""
        return self._pushdown_ex().groupby_where(column, where)

    def agg_where(self, where, column: str, agg: str = "count") -> float:
        """Masked count/sum/mean of ``column`` under a predicate."""
        return self._pushdown_ex().agg_where(where, column, agg)

    # -- streaming convenience -------------------------------------------------------
    def serve_stream(self, row_batches):
        """Featurize an iterator of row-index batches through the pumps.

        Yields (rows, features) in submission order while keeping up to
        ``prefetch`` launches in flight per shard on the pump side.
        """
        def gen():
            # the pumps run the prefetch-deep windows; this FIFO only stops
            # the producer racing ahead of the consumer
            pending: deque[tuple[np.ndarray, int]] = deque()
            for rows in row_batches:
                rows = np.asarray(rows)
                pending.append((rows, self.submit(rows)))
                if len(pending) > self.prefetch:
                    r, t = pending.popleft()
                    yield r, self.result(t)
            while pending:
                r, t = pending.popleft()
                yield r, self.result(t)
        return gen()

    # -- reporting --------------------------------------------------------------
    @property
    def classes(self) -> dict[str, RequestClass]:
        """The registered request classes (always includes 'default')."""
        return dict(self._classes)

    def latency_percentile(self, q: float,
                           klass: str | None = None) -> float:
        """The q-th per-ticket latency percentile in SECONDS from the
        streaming histogram — every completed ticket since construction
        or the last :meth:`reset_latency_window`. ``klass`` narrows to one
        request class."""
        with self._lock:
            h = self._lat_hist if klass is None \
                else self._class_stats[klass]["hist"]
            return h.percentile(q)

    def class_stats(self) -> dict[str, dict]:
        """Per-request-class serving picture: counts (requests /
        completed / failed / pending / rows), the class's streaming
        latency summary (p50/p99/min/max/mean ms over ALL its completed
        tickets) and its queue wait (``queue_p50_ms``/``queue_p99_ms``:
        enqueue to the pump's take, over every chunk taken). JSON-safe —
        what the front door's stats endpoint and the per-class SLO gates
        read."""
        with self._lock:
            out = {}
            for name, cs in self._class_stats.items():
                resolved = cs["completed"] + cs["failed"]
                out[name] = {
                    "requests": cs["requests"],
                    "completed": cs["completed"],
                    "failed": cs["failed"],
                    "pending": max(cs["requests"] - resolved, 0),
                    "rows": cs["rows"],
                    **cs["hist"].summary(),
                    "queue_p50_ms": cs["queue"].percentile(50) * 1e3,
                    "queue_p99_ms": cs["queue"].percentile(99) * 1e3}
            return out

    def reset_latency_window(self) -> None:
        """Start a fresh latency observation window: clears the
        streaming histograms (global, per class and the per-class queue
        waits) and ``stats['latency_samples_total']``.
        The serving ledger (requests/completed/failed counters) is NOT
        touched — this resets what the percentiles COVER (post-warmup
        benching, scrape intervals), not what happened."""
        with self._lock:
            self._lat_hist = LatencyHistogram()
            self.stats["latency_samples_total"] = 0
            for cs in self._class_stats.values():
                cs["hist"] = LatencyHistogram()
                cs["queue"] = LatencyHistogram()

    def throughput_stats(self, wall_s: float) -> dict:
        rows = self.stats["rows"]
        done = self.stats["completed"]
        failed = self.stats["failed_tickets"]
        req = self.stats["requests"]
        resolved = done + failed
        wall_ok = wall_s > 0
        return {**self.stats, "wall_s": wall_s,
                # wall_s <= 0 cannot yield a rate: report 0.0 with the
                # flag set rather than float('inf'), which json.dump
                # renders as the non-standard Infinity token downstream
                # parsers reject
                "wall_s_invalid": not wall_ok,
                "rows_per_s": rows / wall_s if wall_ok else 0.0,
                "mean_latency_s": (self.stats["latency_s_total"] / done
                                   if done else 0.0),
                # the availability the chaos gates assert on: completed
                # over RESOLVED tickets (completed + failed) — calling
                # this mid-flight no longer counts still-pending work as
                # failures; `pending` reports it explicitly
                "pending": max(req - resolved, 0),
                "availability": done / resolved if resolved else 1.0,
                "pad_overhead": (self.stats["padded_rows"] /
                                 max(rows + self.stats["padded_rows"], 1))}
