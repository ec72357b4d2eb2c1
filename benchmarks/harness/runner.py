"""One run of one cell: set-up, correctness, window, the result line."""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import types
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmarks.harness import (
    annotate,
    correct,
    events as ev_mod,
    load,
    spec,
    system,
)


# the open-loop generator's lateness (p99) may be this share of the median
# latency before a run's tails are called the generator's (ISSUE 22)
LATE_SHARE_OF_P50 = 0.05


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def require_devices(chips: int) -> List[Any]:
    """The cell's chips or nothing: no TPU, or another count than the cell
    asks for, exits non-zero before any result is printed."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"this benchmark needs a TPU and JAX found platform "
            f"{devs[0].platform!r} ({devs[0].device_kind}); there is no "
            f"fallback")
    if len(devs) != chips:
        raise SystemExit(f"the cell asks for {chips} chip(s) and JAX found "
                         f"{len(devs)}")
    return devs


def memory_peak_bytes(stats: Dict[str, Any]) -> int:
    """Peak footprint of one chip from ``Device.memory_stats()``: the
    allocator's ``peak_bytes_in_use`` (parameters, inputs, results) plus
    ``peak_bytes_reserved`` (what loaded programs reserve for their
    temporaries). The two are disjoint on the v5e: ``bytes_limit -
    largest_free_block_bytes`` equals their sum (my chip run, PR 22)."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def _percentiles(x: np.ndarray, digits: int = 0) -> str:
    return "/".join(f"{np.percentile(x, q):.{digits}f}" for q in (50, 90, 99))


def _warm(scorer, job, pool: ev_mod.EventPool, buckets: Sequence[int],
          mode: str, max_batch: int, users, merchants,
          timings: Dict[str, float]) -> None:
    """Compile (or load from the cache) every program the window can run:
    the device program of each bucket on every replica, and the host
    feature program, which the program compiles per DISTINCT batch size."""
    import jax

    from realtime_fraud_detection_tpu.features.extract import (
        extract_features_host,
    )
    from realtime_fraud_detection_tpu.features.schema import (
        encode_transactions,
    )

    t0 = time.perf_counter()
    replicas = len(job.pool.replicas) if job.pool is not None else 1
    seq = 0
    for b in buckets:
        pend = []
        for _ in range(replicas):
            recs = pool.materialize(range(seq, seq + b), np.zeros(b), "w")
            seq += b
            pend.append(scorer.dispatch(recs))
        for p in pend:
            scorer.finalize(p)
    timings["warm_device_buckets"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    if mode == "open_loop":
        # below the knee a batch is whatever gathered: any size up to
        # max_batch, each a program of its own on the host backend
        recs = pool.materialize(range(seq, seq + max_batch),
                                np.zeros(max_batch), "w")
        txn = encode_transactions(recs, users, merchants)
        for n in range(1, max_batch + 1):
            extract_features_host(
                jax.tree.map(lambda a: np.asarray(a)[:n], txn))
    timings["warm_host_feature_shapes"] = time.perf_counter() - t0


def run_cell(args: argparse.Namespace, t_process: float) -> Dict[str, Any]:
    # set-up makes millions of long-lived objects (events, profiles); a
    # collector walking them as they are made only lengthens set-up. It is
    # switched on, for good, where the traffic starts (``load.drive``).
    gc.disable()
    cell = spec.cell(args.workload)
    cfg, traffic = cell["config_data"], cell["traffic_data"]
    arrival = spec.arrival(traffic["arrival"])
    mode = arrival.MODE
    traced = bool(args.trace)
    e2e_defs = spec.metrics_for(cell["name"], "end_to_end")
    layer_defs = spec.metrics_for(cell["name"], "per_layer")
    readers = {m["name"]: spec.reader_for(m["name"], kind)
               for kind, defs in (("end_to_end", e2e_defs),
                                  ("per_layer", layer_defs))
               for m in defs}          # every name resolves before any work
    builder = spec.builder(cfg)

    from realtime_fraud_detection_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    import jax

    # the host feature programs compile in well under a second each and
    # there can be hundreds: cache them too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = require_devices(int(cell["chips"]))
    compiles = correct.CompileCounter()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    log(f"cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']} ({mode}), seed {args.seed}, {args.seconds} s, "
        f"trace {int(traced)}; jax {jax.__version__}; device {device}; "
        f"compile cache {cache_dir}")

    # ---- open loop: the load generator is a process of its own, started
    # now so that it builds its copy of the stream while this one sets up
    remote = load.RemoteProducer(cell["name"], args.seed, args.seconds) \
        if mode == "open_loop" else None
    try:
        return _measure(args, t_process, cell, remote, types.SimpleNamespace(
            readers=readers, e2e_defs=e2e_defs, layer_defs=layer_defs,
            builder=builder, devices=devices, device=device,
            compiles=compiles))
    finally:
        if remote is not None:
            remote.close()


def _measure(args: argparse.Namespace, t_process: float,
             cell: Dict[str, Any], remote: Optional[load.RemoteProducer],
             env: types.SimpleNamespace) -> Dict[str, Any]:
    import jax

    cfg, traffic = cell["config_data"], cell["traffic_data"]
    traced = bool(args.trace)
    readers, e2e_defs, layer_defs = env.readers, env.e2e_defs, env.layer_defs
    builder = env.builder
    devices, device, compiles = env.devices, env.device, env.compiles
    warmup_s, grace_s = float(traffic["warmup_s"]), float(traffic["grace_s"])
    timings: Dict[str, float] = {"imports": time.time() - t_process}

    def timed(name: str, t0: float) -> None:
        timings[name] = time.perf_counter() - t0

    # ---- data: population, event pool, the stream's events
    t0 = time.perf_counter()
    made = ev_mod.make_stream(cell, args.seed, args.seconds)
    mode, pool, offsets = made.mode, made.pool, made.offsets
    users = made.population.user_profiles()
    merchants = made.population.merchant_profiles()
    seen = np.minimum(pool.text_tokens, cfg["text_len"])
    log(f"event pool: {len(pool)} distinct events; combined-text tokens "
        f"p50/p90/p99 {_percentiles(pool.text_tokens)} as generated, "
        f"{_percentiles(seen)} as the model sees them "
        f"(text_len {cfg['text_len']})")
    # the producer process holds the open loop's events; a backlog is
    # produced by this one
    stream = pool.materialize(range(len(offsets)), offsets) \
        if remote is None else None
    timed("events", t0)
    log(f"stream: {len(offsets)} events "
        f"({traffic['rate_txn_per_s']} txn/s nominal)")

    # ---- the system under test
    t0 = time.perf_counter()
    sample = pool.materialize(range(512), np.zeros(512), "q")
    models = builder.make_models(
        cfg, args.seed, system.event_features(sample, users, merchants))
    scorer = builder.make_scorer(cfg, args.seed, models, users, merchants)
    broker, job = system.make_job(
        cfg, scorer, traced,
        broker=remote.server.broker if remote is not None else None)
    jax.block_until_ready(models)
    timed("scorer_build", t0)
    _warm(scorer, job, pool, system.buckets_hit(cfg, mode), mode,
          cfg["job"]["max_batch"], users, merchants, timings)

    # ---- (a) parity with the plain reference, outside the window
    t0 = time.perf_counter()
    par = correct.parity(scorer, pool.materialize(
        range(cfg["parity_rows"]), np.zeros(cfg["parity_rows"]), "p"), cfg)
    timed("correctness_check", t0)
    log(f"parity vs the plain float32 reference "
        f"(configs/{cfg['reference']}.py): {par}")

    spans = annotate.install(job, scorer) if traced else None
    trace_dir = os.path.join(str(spec.ROOT), ".bench_trace") if traced \
        else None
    state: Dict[str, Any] = {}

    def on_open() -> None:
        state["setup_s"] = time.time() - t_process
        log(f"window opens; set-up {state['setup_s']:.1f} s: "
            + ", ".join(f"{k} {v:.1f}" for k, v in timings.items()))

    timings["traffic_warmup"] = warmup_s
    run = load.drive(
        mode=mode, job=job, scorer=scorer, broker=broker, events=stream,
        remote=remote,
        due_offsets=offsets, seconds=float(args.seconds), warmup_s=warmup_s,
        grace_s=grace_s, budget_ms=float(traffic["latency_budget_ms"]),
        traced=traced, spans=spans, trace_dir=trace_dir, on_open=on_open,
        compiles=compiles, log=log)
    job.close()
    compiled_in_window = run.extra["compiled_in_window"]
    run.extra.update(
        setup_s=state["setup_s"], cfg=cfg, traffic=traffic, device=device,
        vocabulary=builder.VOCABULARY,
        flops_per_batch=builder.matmul_flops_per_batch(cfg))
    fullest = max((d.memory_stats() or {} for d in devices),
                  key=memory_peak_bytes)
    run.extra["memory_peak_bytes"] = memory_peak_bytes(fullest)

    # ---- counts and checks
    w = run.in_window()
    failed = int(run.failed().sum())
    c = run.counters
    if mode == "open_loop":
        attempted = int(w.sum())
        accounted = (run.gateway_dropped == 0
                     and bool(np.isfinite(run.ingested[w]).all()))
        detail = (f"{attempted} due in the window, gateway dropped "
                  f"{run.gateway_dropped}, "
                  f"{int((~np.isfinite(run.ingested[w])).sum())} never "
                  f"reached the input topic")
    else:
        attempted = c["scored"] + c["errors"] + c["shed"]
        emitted_n = int(((run.emitted >= run.t_open)
                         & (run.emitted <= run.t_count_snap)).sum())
        accounted = emitted_n == attempted
        detail = f"counters say {attempted}, predictions topic {emitted_n}"
    checks = [
        ("parity with the plain float32 reference", par["ok"],
         par["max_delta"]),
        ("counters['errors'] == 0", c["errors"] == 0
         and run.counters_slice.get("errors", 0) == 0, c),
        ("every emitted prediction finite, on the ladder, unmarked",
         run.bad_outputs == 0 and not run.marked_failed.any(),
         f"{run.bad_outputs} bad, {int(run.marked_failed.sum())} marked"),
        ("every attempted transaction accounted for", accounted, detail),
        ("each transaction emitted once on the predictions topic",
         run.duplicates == 0, f"{run.duplicates} duplicates"),
        ("zero compilations inside the window", compiled_in_window == 0,
         compiled_in_window),
    ]
    if mode == "backlog":
        checks.append(("the backlog outlasted the window", run.lag_end > 0,
                       f"lag {run.lag_start} -> {run.lag_end}"))
    for name, ok, detail in checks:
        log(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})")
    log(f"lag at window open {run.lag_start}, at its end {run.lag_end}; "
        f"batches {c['batches']}, close reasons {run.close_reasons}; "
        f"token cache {run.token_cache}; host stages (ms mean) "
        + str({k: round(v['mean_ms'], 3) for k, v in run.stages.items()}))
    gcs = run.extra["collector"]
    log(f"cyclic collector in the job's process, counted part of the "
        f"window: {gcs['seconds']:.3f} s of {run.counted_s:.1f} s, longest "
        f"pause {gcs['longest_s'] * 1e3:.1f} ms, runs per generation "
        f"{gcs['runs']}; full collections as (end, seconds) from the "
        f"window's opening: "
        + str([(round(end - run.t_open, 3), round(took, 3))
               for end, took in gcs["full"]]))
    if run.pool_completed is not None:
        log(f"pool completed per device: {run.pool_completed}")
    if mode == "open_loop":
        late = (run.submitted[w] - run.due[w]) * 1e3
        late = late[np.isfinite(late)]
        log(f"generator lateness (submit - due) ms p50/p90/p99 "
            f"{_percentiles(late, 2)}, max {late.max():.2f}, over "
            f"{len(late)} events")
        lat = (run.emitted[w] - run.due[w]) * 1e3
        lat = lat[np.isfinite(lat)]
        log(f"due -> emitted ms p50/p90/p99 {_percentiles(lat, 1)}, p95 "
            f"{np.percentile(lat, 95):.1f}, max {lat.max():.1f} (emitted "
            f"ones only; the metrics count failures as misses)")
        late99, p50 = np.percentile(late, 99), np.percentile(lat, 50)
        if late99 > LATE_SHARE_OF_P50 * p50:
            log(f"WARNING: THE LOAD GENERATOR RAN LATE: its lateness p99 "
                f"{late99:.1f} ms is over {LATE_SHARE_OF_P50:.0%} of the "
                f"median latency {p50:.1f} ms (ISSUE 22's limit) and "
                f"{late99 / np.percentile(lat, 99):.1%} of the latency p99; "
                f"lateness is inside every latency")
    log(f"memory_stats of the fullest device: {fullest}")
    if run.tracer is not None:
        q = run.tracer.breakdown().get("quantiles", {})
        log("tracer stage means at the e2e quantiles (ms): " + str(
            {k: v["stage_ms"] for k, v in q.items()}))
    if run.trace is not None:
        log(f"trace: window {run.trace['window_s']:.3f} s, busy per device "
            f"{run.trace['per_device']}, annotations "
            f"{run.trace['annotations']}")

    # ---- metrics, each through its own reader
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in (layer_defs if traced else e2e_defs):
        value = readers[m["name"]](run)
        if value is None:
            log(f"metric {m['name']}: nothing to read, left out")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    lat = run.extra.get("latency_samples")
    if lat is not None:
        log(f"latency samples {lat}")

    out: Dict[str, Any] = {
        "correct": all(bool(ok) for _, ok, _ in checks),
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "device": dict(device,
                       memory_peak_bytes=run.extra["memory_peak_bytes"]),
    }
    if run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    return out


def main(argv: Optional[Sequence[str]] = None,
         t_process: Optional[float] = None) -> int:
    t_process = time.time() if t_process is None else t_process
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_cell(args, t_process)
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0
