"""Drive the packed feature-serving path once on a TPU and check every answer.

One process, one chip by default. The data is generated from ``--seed`` in
the shape of the mixed-cardinality serving schema (``age``, ``state``,
``income``, ``device``; zscore, bucketize, one-hot, minmax, log, one-hot),
compiled into a ``FeaturePlan(packed=True)`` and served through
``FeatureService`` behind a ``FeatureFrontend`` with the default request
classes. Phases: arbitrary-row requests, predicate-filtered requests,
aggregates, an append + ``refresh()``, and the Pallas kernels that compile
for the device, each checked bit-exact against the split XLA path. Every
served row must equal ``FeaturePlan.host_features`` bit for bit, and a numpy
recomputation from the raw values within ``allclose(rtol=1e-6)`` (exactly
for one-hot and bucketize). Any host fallback, retry, device loss or failed
ticket fails the run.

The per-phase seconds it prints are smoke timings, not benchmark numbers.
The last line of standard output is one JSON object naming the device.

    python chip_smoke.py                 # one TPU chip, 128M rows
    python chip_smoke.py --chips 4       # mesh-sharded service on 4 chips
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --rows 65536

Without ``--rehearse`` it exits non-zero, printing no result, unless JAX's
first device is a TPU.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import contextlib
import itertools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.columnar import Table  # noqa: E402
from repro.columnar.query import between, eq, isin  # noqa: E402
from repro.core import FeaturePlan, FeatureSet  # noqa: E402
from repro.core.pipeline import FeatureExecutor  # noqa: E402
from repro.kernels import KernelRefused  # noqa: E402
from repro.kernels.adv_gather import ops as adv_ops  # noqa: E402
from repro.kernels.bitunpack import bitunpack  # noqa: E402
from repro.kernels.predicate_scan import ops as scan_ops  # noqa: E402
from repro.serve import FeatureFrontend, FeatureService  # noqa: E402
from repro.serve.classes import default_classes  # noqa: E402

DEFAULT_ROWS = 1 << 27          # 256 IMCUs of 512K rows
REQUEST_ROWS = 512
N_REQUESTS = 256
WAVE = 32                       # requests in flight per admission wave
APPEND_ROWS = 4096
BOUNDARIES = (30.0, 45.0, 65.0)
# every counter that is nonzero only when serving left the device path
FALLBACK_STATS = ("retries", "failovers", "timeouts", "failed_tickets",
                  "devices_lost", "host_gathers")
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
# (predicate, the same predicate row by row over the raw values): an AND of
# ranges and an IN-set select about 8K and 1K rows of 128M; the aggregate
# predicate selects about a fifth of the table
P_AND = (between("age", 30, 31) & between("income", 100_000, 101_000)
         & eq("device", 2),
         lambda r: ((r["age"] >= 30) & (r["age"] <= 31)
                    & (r["income"] >= 100_000) & (r["income"] <= 101_000)
                    & (r["device"] == 2)))
P_IN = (isin("state", [3, 17, 41]) & isin("age", [25, 50])
        & eq("income", 120_000),
        lambda r: (np.isin(r["state"], [3, 17, 41])
                   & np.isin(r["age"], [25, 50]) & (r["income"] == 120_000)))
P_AGG = (between("age", 30, 60) & isin("device", [0, 3]),
         lambda r: (r["age"] >= 30) & (r["age"] <= 60)
         & np.isin(r["device"], [0, 3]))


class SmokeFailure(AssertionError):
    """A phase produced a wrong answer or left the device path."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def features() -> FeatureSet:
    return (FeatureSet().add("age", "zscore")
            .add("age", "bucketize", boundaries=BOUNDARIES)
            .add("state", "onehot")
            .add("income", "minmax").add("income", "log")
            .add("device", "onehot"))


def generate(rng: np.random.Generator, n: int) -> dict[str, np.ndarray]:
    """The serving schema's raw columns in narrow dtypes."""
    return {"age": rng.integers(18, 90, n, dtype=np.int8),
            "state": rng.integers(0, 50, n, dtype=np.int8),
            "income": rng.integers(20, 250, n, dtype=np.int32) * 1000,
            "device": rng.integers(0, 4, n, dtype=np.int8)}


def reference_features(raw: dict[str, np.ndarray],
                       rows: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(continuous, exact) features of ``rows`` recomputed from raw values.

    ``continuous`` holds zscore, minmax and log (compared within rtol);
    ``exact`` holds bucketize and both one-hots (compared exactly)."""
    counts = np.bincount(raw["age"].astype(np.int64))
    ages = np.flatnonzero(counts).astype(np.float64)
    c = counts[counts > 0]
    mean = float(np.dot(ages, c) / c.sum())
    std = float(np.sqrt(np.dot((ages - mean) ** 2, c) / c.sum()))
    lo, hi = float(raw["income"].min()), float(raw["income"].max())
    a = raw["age"][rows].astype(np.float64)
    inc = raw["income"][rows].astype(np.float64)
    continuous = np.stack([(a - mean) / std, (inc - lo) / (hi - lo),
                           np.log1p(inc)], axis=1)

    def onehot(col):
        # dictionary codes follow each value's first appearance; a prefix
        # that holds every value of the column gives the same order
        n_values = np.count_nonzero(np.bincount(raw[col]))
        for m in (1 << 20, raw[col].size):
            uniq, first = np.unique(raw[col][:m], return_index=True)
            if uniq.size == n_values:
                break
        code_of = np.empty(int(uniq.max()) + 1, np.int64)
        code_of[uniq[np.argsort(first)]] = np.arange(uniq.size)
        out = np.zeros((rows.size, uniq.size), np.float32)
        out[np.arange(rows.size), code_of[raw[col][rows]]] = 1.0
        return out

    bucket = np.searchsorted(np.asarray(BOUNDARIES), a, side="right")
    exact = np.concatenate([bucket[:, None].astype(np.float32),
                            onehot("state"), onehot("device")], axis=1)
    return continuous, exact


def check_against_raw(raw, rows: np.ndarray, feats: np.ndarray,
                      what: str) -> None:
    """Feature layout: age.zscore, age.bucketize, state.onehot (K_s),
    income.minmax, income.log, device.onehot (K_d)."""
    ks = feats.shape[1] - 8
    continuous, exact = reference_features(raw, rows)
    got_cont = feats[:, [0, 2 + ks, 3 + ks]]
    got_exact = np.concatenate([feats[:, 1:2 + ks], feats[:, 4 + ks:]],
                               axis=1)
    # the ADV tables are float32 arithmetic over float64 statistics: a
    # zscore near 0 subtracts a float32-rounded mean, an absolute error of
    # a few float32 ulps of the operands (~1e-7 here), hence the atol
    check(np.allclose(got_cont, continuous, rtol=1e-6, atol=1e-6),
          f"{what}: zscore/minmax/log differ from the raw recomputation")
    check(np.array_equal(got_exact, exact),
          f"{what}: bucketize/one-hot differ from the raw recomputation")


class PhaseClock:
    """Wall and compile seconds per phase (JAX's own compile events,
    persistent-cache lookups included)."""

    def __init__(self):
        self.compile_s = 0.0
        self.rows: list[tuple[str, str, float, float]] = []

    def _listen(self, event: str, secs: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.compile_s += secs

    def __enter__(self) -> "PhaseClock":
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._listen)

    @contextlib.contextmanager
    def phase(self, name: str, path: str):
        c0, t0 = self.compile_s, time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        self.rows.append((name, path, wall, comp))
        print(f"phase {name}: path={path} wall_s={wall:.3f} "
              f"compile_s={comp:.3f}  (smoke timing, not a benchmark)",
              flush=True)


@contextlib.contextmanager
def launch_devices():
    """Record the device of every indexed-gather launch buffer (the one
    launch the service pump issues per coalesced group)."""
    seen: collections.Counter = collections.Counter()
    launch = FeatureExecutor._rows_future

    def recording(self, rows):
        out = launch(self, rows)
        seen.update(out.devices())
        return out

    FeatureExecutor._rows_future = recording
    try:
        yield seen
    finally:
        FeatureExecutor._rows_future = launch


def check_service_clean(svc: FeatureService, wall_s: float,
                        what: str) -> None:
    stats = svc.throughput_stats(wall_s)
    bad = {k: stats[k] for k in FALLBACK_STATS if stats[k]}
    print(f"{what} stats: launches={stats['launches']} "
          f"requests={stats['requests']} completed={stats['completed']} "
          + " ".join(f"{k}={stats[k]}" for k in FALLBACK_STATS)
          + f" availability={stats['availability']}", flush=True)
    check(not bad, f"{what} left the device path: {bad}")
    check(stats["availability"] == 1.0, f"{what} availability "
          f"{stats['availability']} < 1.0")
    check(stats["launches"] > 0, f"{what} launched nothing")


def memory_line(dev) -> str:
    stats = dev.memory_stats()
    if not stats:
        return "device memory_stats: not reported by this backend"
    return (f"device memory_stats: bytes_in_use={stats.get('bytes_in_use')} "
            f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def build(args, imcu_rows: int | None = None):
    t0 = time.perf_counter()
    raw = generate(np.random.default_rng(args.seed), args.rows)
    t1 = time.perf_counter()
    table = Table.from_data(raw, imcu_rows=imcu_rows)
    t2 = time.perf_counter()
    plan = FeaturePlan(table, features(), packed=True)
    t3 = time.perf_counter()
    print(f"rows={plan.n_rows} imcus={table['age'].n_imcus} "
          f"device_bits={plan.device_bits} out_dim={plan.out_dim}")
    print(f"host build: generate_s={t1 - t0:.1f} table_s={t2 - t1:.1f} "
          f"plan_s={t3 - t2:.1f}", flush=True)
    return raw, table, plan


async def _featurize_wave(front, waves):
    return await asyncio.gather(*(front.featurize(r, klass=k)
                                  for r, k in waves))


def serve_rows(front, plan, raw, requests, klasses) -> None:
    """Half the requests through submit/result, half through featurize;
    every answer bit-exact with the host gather, a sample against raw."""
    half = len(requests) // 2
    for w in range(0, len(requests), WAVE):
        wave = list(zip(requests[w:w + WAVE], klasses[w:w + WAVE]))
        if w < half:
            tickets = [front.submit(r, klass=k) for r, k in wave]
            outs = [front.result(t, timeout=600) for t in tickets]
        else:
            outs = asyncio.run(_featurize_wave(front, wave))
        for (r, _), out in zip(wave, outs):
            check(np.array_equal(out, plan.host_features(r)),
                  "served rows differ from FeaturePlan.host_features")
    sample = np.concatenate(requests[::16])
    want = plan.host_features(sample)
    check_against_raw(raw, sample, want, "served rows")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=DEFAULT_ROWS,
                    help="table rows (shrink only for --rehearse)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded path and the "
                    "one-device service it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a non-TPU backend (CPU rehearsal, Pallas "
                    "kernels interpreting)")
    args = ap.parse_args(argv)
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX's first device is {platform!r}, not a TPU "
              "(pass --rehearse to run on it anyway)", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" JAX has {len(devs)}", file=sys.stderr)
        return 1
    print(f"devices: {len(devs)} x {devs[0].device_kind} ({platform})")
    with PhaseClock() as clock:
        if args.chips == 4:
            four_chips(args, clock, devs[:4])
        else:
            one_chip(args, clock, devs[0])
        total_compile = sum(r[3] for r in clock.rows)
        total_wall = sum(r[2] for r in clock.rows)
        print(f"total: wall_s={total_wall:.3f} compile_s={total_compile:.3f}"
              "  (smoke timing, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


def one_chip(args, clock: PhaseClock, dev) -> None:
    raw, table, plan = build(args)
    n = plan.n_rows
    rng = np.random.default_rng(args.seed + 1)
    requests = [rng.integers(0, n, REQUEST_ROWS) for _ in range(N_REQUESTS)]
    klasses = ["interactive", "batch"] * (N_REQUESTS // 2)
    svc = FeatureService(plan, classes=default_classes())
    ex = FeatureExecutor(plan)              # split path, for direct checks
    t_start = time.perf_counter()
    with svc, launch_devices() as seen:
        front = FeatureFrontend(svc)
        print(f"resident packed words: {ex.resident_bytes()} bytes per "
              f"executor; {memory_line(dev)}", flush=True)
        with clock.phase("warmup", "split"):
            for k in ("background", "batch", "interactive"):
                front.result(front.submit(requests[0], klass=k), timeout=900)
        with clock.phase("rows", "split"):
            serve_rows(front, plan, raw, requests, klasses)
        with clock.phase("where", "split"):
            for pred, on_raw in (P_AND, P_IN):
                want_rows = np.flatnonzero(on_raw(raw))
                got = front.result(front.submit(where=pred, klass="batch"),
                                   timeout=600)
                check(np.array_equal(got, plan.host_features(want_rows)),
                      "filtered request differs from the raw mask's rows")
                check(np.array_equal(svc.filtered_rows(pred), want_rows),
                      "filtered rows differ from the raw mask")
                print(f"  where: {want_rows.size} rows match")
        with clock.phase("aggregates", "split"):
            check_aggregates(svc, raw, P_AGG)
        with clock.phase("refresh", "split"):
            raw = append_and_serve(front, svc, table, plan, raw, rng)
        check_service_clean(svc, time.perf_counter() - t_start, "service")
        print(memory_line(dev), flush=True)
        check_launch_devices(seen, dev.platform, {dev})
    refused = kernel_phase(clock, plan, ex, requests[:8], raw)
    for name, why in refused.items():
        print(f"kernel refused by the compiler: {name}: {why}")


def check_launch_devices(seen, platform: str, devices) -> None:
    print("launches per device: " + ", ".join(
        f"{d.platform}:{d.id}={c}" for d, c in sorted(
            seen.items(), key=lambda kv: kv[0].id)), flush=True)
    check(sum(seen.values()) > 0, "no launch was recorded")
    check({d.platform for d in seen} == {platform},
          f"launch results on {sorted({d.platform for d in seen})}, "
          f"not only {platform}")
    check(set(seen) <= set(devices), "a launch ran off the serving devices")


def check_aggregates(svc, raw, pred_on_raw) -> None:
    pred, on_raw = pred_on_raw
    mask = on_raw(raw)
    count = int(mask.sum())
    income = raw["income"][mask].astype(np.float64)
    check(svc.count_where(pred) == count, "count_where differs from numpy")
    check(svc.agg_where(pred, "income", "sum") == income.sum(),
          "agg_where sum differs from numpy")
    check(svc.agg_where(pred, "income", "mean") == income.sum() / count,
          "agg_where mean differs from numpy")
    values, counts = svc.groupby_where("state", pred)
    want = np.bincount(raw["state"][mask].astype(np.int64),
                       minlength=int(values.max()) + 1)[values]
    check(np.array_equal(counts, want), "groupby_where differs from numpy")
    print(f"  aggregates: count={count} sum={income.sum()}")


def append_and_serve(front, svc, table, plan, raw, rng):
    """Append rows (with one new age and one new income value, so two
    columns' ADV tables rebuild), refresh, and serve old and new rows."""
    extra = generate(rng, APPEND_ROWS)
    extra["age"][0] = 95
    extra["income"][1] = 251_000
    n0 = plan.n_rows
    svc.drain()
    new_codes = {c: table[c].dictionary.add_rows(v) for c, v in extra.items()}
    refreshed = plan.refresh(new_codes)
    raw = {c: np.concatenate([raw[c], extra[c]]) for c in raw}
    check(plan.n_rows == n0 + APPEND_ROWS, "refresh did not append")
    rows = np.concatenate([np.arange(n0, n0 + APPEND_ROWS),
                           rng.integers(0, n0, REQUEST_ROWS)])
    got = front.result(front.submit(rows, klass="batch"), timeout=600)
    check(np.array_equal(got, plan.host_features(rows)),
          "rows after refresh differ from FeaturePlan.host_features")
    check_against_raw(raw, rows, got, "rows after refresh")
    print(f"  refresh: {refreshed} column tables rebuilt, "
          f"{APPEND_ROWS} rows appended, n_rows={plan.n_rows}")
    return raw


def kernel_phase(clock, plan, ex, requests, raw) -> dict[str, str]:
    """Ask the executor for its Pallas kernels; run each kernel that
    compiles over the same plan and requests, bit-exact against the split
    path. Returns the refused kernels with the compiler's reasons."""
    refused: dict[str, str] = {}
    try:
        FeatureExecutor(plan, use_kernel=True, commit=False)
    except KernelRefused as e:
        refused = e.refused
    ex.ensure_range_capacity(plan.n_rows)       # cover the appended rows
    dbs = tuple(plan.device_bits)
    flat, offs, n = ex._flat_words, ex._word_offs, plan.n_rows
    if "adv_gather_packed_rows" not in refused:
        with clock.phase("kernel:adv_gather_packed_rows", "kernel"):
            f = plan.fused_tables()
            for r in requests:
                got = adv_ops.adv_gather_packed_rows(
                    flat, offs, dbs, f.table, f.row_offsets, f.card_limits,
                    r.astype(np.int32), f.out_dim)
                check(np.array_equal(np.asarray(got),
                                     np.asarray(ex.batch(r))),
                      "rows kernel differs from the split gather")
    masks = []
    for pred, _ in (P_AND, P_IN, P_AGG):
        split = ex.predicate_mask(pred)
        check({d.platform for d in split.devices()}
              == {d.platform for d in flat.devices()},
              "scan mask left the serving device")
        masks.append((pred, split))
    if "predicate_scan" not in refused:
        with clock.phase("kernel:predicate_scan", "kernel"):
            for pred, split in masks:
                terms, combine, _ = ex._compiled_pred(pred)
                got = scan_ops.predicate_scan(flat, offs, dbs, terms, n,
                                              combine)
                check(np.array_equal(np.asarray(got), np.asarray(split)),
                      "predicate_scan kernel differs from the split scan")
    if "masked_hist" not in refused:
        with clock.phase("kernel:masked_hist", "kernel"):
            for (_, split), c in itertools.product(masks,
                                                   ("state", "income")):
                ci = plan.columns.index(c)
                k = plan.augmented[c].dictionary.cardinality
                args = (flat, offs[ci], dbs[ci], split, k, n)
                check(np.array_equal(
                    np.asarray(scan_ops.masked_counts(*args,
                                                      use_kernel=True)),
                    np.asarray(scan_ops.masked_counts(*args))),
                    "masked_hist kernel differs from the split counts")
    with clock.phase("kernel:bitunpack", "kernel"):
        ci = plan.columns.index("age")       # 8-bit device words
        got = bitunpack(flat[offs[ci]:offs[ci] + n // 4 + 1], 8, n)
        # little-endian 8-bit fields are the words' bytes, in row order
        codes = plan.packed_words[ci].view(np.uint8)[:n]
        check(np.array_equal(np.asarray(got), codes),
              "bitunpack kernel differs from the host words")
    return refused


def four_chips(args, clock: PhaseClock, devs) -> None:
    """The mesh path alone: a sharded service over >= 16 IMCU shards
    round-robin on four chips plus one replica of a hot shard, against a
    one-device service over the same plan."""
    shards = 16
    if args.rows % (shards * 32):
        raise SystemExit(f"--rows must be a multiple of {shards * 32}")
    raw, _table, plan = build(args, imcu_rows=args.rows // shards)
    n = plan.n_rows
    rng = np.random.default_rng(args.seed + 1)
    requests = [rng.integers(0, n, REQUEST_ROWS) for _ in range(64)]
    t_start = time.perf_counter()
    with FeatureService(plan, sharded=True, devices=devs) as mesh, \
            FeatureService(plan) as one, launch_devices() as seen:
        print(f"mesh service: {mesh.n_shards} shards over {len(devs)} "
              "devices", flush=True)
        with clock.phase("mesh:replica", "split"):
            mesh.add_replica(0)
        with clock.phase("mesh:rows", "split"):
            for svc in (mesh, one):        # warm both launch shapes
                svc.result(svc.submit(requests[0]), timeout=900)
            tm = [mesh.submit(r) for r in requests]
            to = [one.submit(r) for r in requests]
            for r, a, b in zip(requests, tm, to):
                got, ref = mesh.result(a, timeout=600), one.result(b,
                                                                   timeout=600)
                check(np.array_equal(got, ref),
                      "mesh service differs from the one-device service")
                check(np.array_equal(got, plan.host_features(r)),
                      "mesh service differs from FeaturePlan.host_features")
        with clock.phase("mesh:where", "split"):
            pred, on_raw = P_AND
            want_rows = np.flatnonzero(on_raw(raw))
            a = mesh.result(mesh.submit(where=pred), timeout=600)
            b = one.result(one.submit(where=pred), timeout=600)
            check(np.array_equal(a, b) and np.array_equal(
                a, plan.host_features(want_rows)),
                "filtered mesh request differs from the one-device service")
            pred, on_raw = P_AGG
            check(mesh.count_where(pred) == one.count_where(pred)
                  == int(on_raw(raw).sum()),
                  "mesh count_where differs from the one-device service")
        check_service_clean(mesh, time.perf_counter() - t_start, "mesh")
        check_service_clean(one, time.perf_counter() - t_start, "one-device")
        per_dev = mesh.device_bytes()
        print("resident words per device: " + ", ".join(
            f"{d.platform}:{d.id}={per_dev.get(id(d), 0)}" for d in devs)
            + f"; replicas={mesh.replicas}", flush=True)
        check(all(per_dev.get(id(d), 0) > 0 for d in devs),
              "a device holds no resident words")
        check_launch_devices(seen, devs[0].platform, devs)
        for d in devs:
            print(f"device {d.id}: {memory_line(d)}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    sys.exit(main())
