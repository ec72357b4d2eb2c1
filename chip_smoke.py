"""The standing proof that the scoring path starts on the chip.

``python chip_smoke.py`` demands one TPU chip (it sets no platform itself
and has no fallback: without a TPU it exits non-zero and prints no result)
and drives the main path once, through the entry points a user calls, at
the full width of the text model the repo deploys — ``FraudScorer(config,
bert_config=BertConfig(), scorer_config=ScorerConfig(text_len=64))`` over
the simulator's default population, weights and traffic made from
``--seed``:

  serve     a ServingApp on an ephemeral port answers /predict and
            /batch-predict over HTTP
  stream    >= 4,096 generated transactions through IngressGateway ->
            InMemoryBroker -> StreamJob at max_batch 256, zero errors
  kernels   the scorer with QuantSettings.full()+KernelSettings.full():
            compiled (not interpreted) Pallas sites, counted, within
            kernel-drill's noise bound of the kernels-off scorer, and
            GEMM-form trees that pick the gather path's leaves
  parity    one bucket-256 batch: the packed program on the chip against
            the same program on the host CPU backend of this process

``--chips 4`` runs ONLY the multi-chip path and what it is compared with:
DevicePool over four devices (bit-identical to single-device scoring of
the same batches) and MeshExecutor on 4x1 and 2x2 meshes (bit-identical to
single-device scoring of each data shard — see ``multichip_phase``).

Earlier stdout lines carry what is worth keeping (versions, compile cache
state, per-bucket compile seconds — set-up, never speed —, the native
build, kernel site counts, peak device memory). The last stdout line is
``{"ok": true, "device": {"platform", "kind", "count"}}``; any phase
failing makes it ``"ok": false`` and the exit code non-zero.

The phases are functions of a ``SmokeSize`` so the same code rehearses on
the CPU at ``TINY`` (tests/test_chip_smoke.py); ``main()`` takes no size
or platform option.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import http.client
import json
import os
import sys
import threading
import time
import traceback
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from realtime_fraud_detection_tpu.models.bert import BertConfig, TINY_CONFIG

_T0 = time.monotonic()
# the bf16 tolerance the repo's parity tests use (tests/test_text.py,
# tests/test_quant.py): absolute, on probabilities
BF16_ATOL = 2e-3


@dataclasses.dataclass(frozen=True)
class SmokeSize:
    """Everything a phase needs to know about how big to be."""

    bert_config: BertConfig
    text_len: int
    num_users: int
    num_merchants: int
    n_predict: int          # /predict requests
    n_batch_predict: int    # transactions in the one /batch-predict
    n_stream: int           # transactions through the StreamJob
    max_batch: int          # StreamJob microbatch bound (and parity batch)
    kernel_buckets: Tuple[int, ...]   # batch sizes compared kernels on/off


# the model the repo deploys: DistilBERT-base widths, simulator defaults
FULL = SmokeSize(BertConfig(), 64, 10_000, 5_000, 48, 32, 4096, 256,
                 (1, 32, 256))
# CPU rehearsal (interpret-mode Pallas is grid-count bound: keep it small)
TINY = SmokeSize(TINY_CONFIG, 32, 64, 16, 6, 4, 64, 32, (1, 32))


def log(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


class PhaseFailed(Exception):
    pass


class Checks:
    """Named pass/fail lines for one phase; ``done`` raises if any failed
    (after all of them were printed — a chip call is too dear to stop at
    the first)."""

    def __init__(self, phase: str):
        self.phase = phase
        self.failed: List[str] = []

    def check(self, name: str, ok: bool, detail: Any = "") -> bool:
        ok = bool(ok)
        log(f"  [{self.phase}] {name}: {'ok' if ok else 'FAILED'}"
            f"{f' ({detail})' if detail != '' else ''}")
        if not ok:
            self.failed.append(name)
        return ok

    def done(self) -> None:
        if self.failed:
            raise PhaseFailed(f"{self.phase}: {', '.join(self.failed)}")


# ------------------------------------------------------------------ builders
def make_generator(size: SmokeSize, seed: int):
    from realtime_fraud_detection_tpu.sim.simulator import (
        TransactionGenerator,
    )

    return TransactionGenerator(num_users=size.num_users,
                                num_merchants=size.num_merchants, seed=seed)


def make_scorer(size: SmokeSize, seed: int, gen, *, quant: bool = False,
                kernels: bool = False, devices: Optional[Sequence] = None,
                models=None):
    """The seam ``rtfd serve`` builds its scorer through, on ``devices``
    (default: the first device only — one chip, whatever the host holds)."""
    import jax

    from realtime_fraud_detection_tpu.core.mesh import build_mesh
    from realtime_fraud_detection_tpu.scoring import FraudScorer, ScorerConfig
    from realtime_fraud_detection_tpu.utils.config import (
        Config,
        KernelSettings,
        QuantSettings,
    )

    config = Config()
    # the dedicated metrics listener binds a FIXED port; the smoke serves
    # /metrics on the app's own ephemeral one
    config.monitoring.prometheus_port = 0
    if quant:
        config.quant = QuantSettings.full()
    if kernels:
        config.kernels = KernelSettings.full()
    scorer = FraudScorer(
        config, models=models, bert_config=size.bert_config,
        scorer_config=ScorerConfig(text_len=size.text_len), seed=seed,
        mesh=build_mesh(devices=list(devices) if devices is not None
                        else jax.devices()[:1]))
    scorer.seed_profiles(gen.users.profiles(), gen.merchants.profiles())
    return scorer


def warm_buckets(scorer, gen, label: str,
                 buckets: Optional[Sequence[int]] = None) -> Dict[int, float]:
    """Compile every bucket through dispatch/finalize before anything
    waits on a deadline (a first compile is far longer than the 5 s
    prediction timeout). Seconds are set-up time, reported as such."""
    from realtime_fraud_detection_tpu.core.batching import BATCH_BUCKETS

    secs: Dict[int, float] = {}
    for b in buckets or BATCH_BUCKETS:
        t0 = time.perf_counter()
        scorer.finalize(scorer.dispatch(gen.generate_batch(b)))
        secs[b] = round(time.perf_counter() - t0, 2)
    log(f"  [{label}] set-up: first call per bucket (compile included), "
        f"seconds: {secs}")
    return secs


def _result_ok(res: Dict[str, Any]) -> bool:
    from realtime_fraud_detection_tpu.features.rules import DECISIONS

    p = res.get("fraud_probability")
    return (isinstance(p, float) and np.isfinite(p) and 0.0 <= p <= 1.0
            and res.get("decision") in DECISIONS
            and res.get("risk_level") != "ERROR")


# --------------------------------------------------------------- serve phase
def serve_phase(scorer, gen, size: SmokeSize) -> Dict[str, Any]:
    """A live ServingApp answers /predict and /batch-predict over HTTP."""
    from realtime_fraud_detection_tpu.serving import ServingApp

    c = Checks("serve")
    app = ServingApp(scorer.config, scorer=scorer, host="127.0.0.1", port=0)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def run() -> None:
        asyncio.set_event_loop(loop)

        async def _start() -> None:
            await app.start()
            started.set()

        loop.run_until_complete(_start())
        loop.run_forever()

    thread = threading.Thread(target=run, name="smoke-serve", daemon=True)
    thread.start()
    if not started.wait(timeout=60):
        raise PhaseFailed("serve: the app did not start within 60 s")

    def request(method: str, path: str, body: Any = None) -> Tuple[int, Any]:
        conn = http.client.HTTPConnection("127.0.0.1", app.port, timeout=60)
        try:
            payload = json.dumps(body) if body is not None else None
            conn.request(method, path, body=payload,
                         headers={"Content-Type": "application/json"}
                         if payload else {})
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read())
        finally:
            conn.close()

    try:
        txns = gen.generate_batch(size.n_predict)
        # a dedicated client pool: the app runs its scoring stages on the
        # asyncio default executor, which blocking clients must not occupy
        with ThreadPoolExecutor(max_workers=8,
                                thread_name_prefix="smoke-client") as pool:
            answers = list(pool.map(
                lambda t: request("POST", "/predict", t), txns))
        bad = [(s, r) for s, r in answers if s != 200 or not _result_ok(r)]
        c.check(f"{len(answers)} /predict answered 200 with a finite "
                f"probability and a ladder decision", not bad,
                bad[:1] if bad else "")
        batch = gen.generate_batch(size.n_batch_predict)
        status, data = request("POST", "/batch-predict",
                               {"transactions": batch})
        results = data.get("results", []) if isinstance(data, dict) else []
        c.check("/batch-predict answered 200 for every transaction",
                status == 200 and len(results) == len(batch)
                and all(_result_ok(r) for r in results),
                f"status {status}, {len(results)}/{len(batch)} results")
        status, health = request("GET", "/health")
        c.check("/health", status == 200
                and health.get("status") == "healthy", health)
    finally:
        asyncio.run_coroutine_threadsafe(app.stop(), loop).result(timeout=30)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(timeout=10)
        loop.close()
    c.check("server thread stopped", not thread.is_alive())
    c.done()
    return {"predict": len(answers), "batch_predict": len(results)}


# -------------------------------------------------------------- stream phase
def stream_phase(scorer, gen, size: SmokeSize) -> Dict[str, Any]:
    """Generated traffic -> IngressGateway -> broker -> StreamJob."""
    from realtime_fraud_detection_tpu.native import native_build_error
    from realtime_fraud_detection_tpu.stream import (
        IngressGateway,
        InMemoryBroker,
        JobConfig,
        StreamJob,
    )
    from realtime_fraud_detection_tpu.stream import topics as T

    c = Checks("stream")
    broker = InMemoryBroker()
    job = StreamJob(broker, scorer, JobConfig(max_batch=size.max_batch))
    # the production call site of the native (C++) ingest queue; without a
    # toolchain it serves from the Python deque — visible here, no failure
    gateway = IngressGateway(broker, T.TRANSACTIONS)
    log(f"  [stream] ingest queue: "
        f"{'native C++' if gateway.native else 'python deque'}"
        f" (native build error: {native_build_error()})")
    try:
        for txn in gen.generate_batch(size.n_stream):
            while not gateway.submit(txn):      # ring full: backpressure
                time.sleep(0.001)
    finally:
        gateway.close()
    c.check("gateway delivered every transaction",
            gateway.sent == size.n_stream and gateway.dropped == 0,
            f"sent {gateway.sent}, dropped {gateway.dropped}")
    scored = job.run_until_drained()
    job.close()
    preds = [r.value for r in broker.consumer(
        [T.PREDICTIONS], "chip-smoke").poll(size.n_stream + 1)]
    c.check("counters['scored'] equals the count",
            scored == size.n_stream
            and job.counters["scored"] == size.n_stream,
            f"{job.counters['scored']}/{size.n_stream}")
    c.check("counters['errors'] == 0", job.counters["errors"] == 0,
            job.counters)
    bad = [p for p in preds if not _result_ok(p)]
    c.check("every emitted prediction is finite, on the ladder and "
            "carries no error marker",
            len(preds) == size.n_stream and not bad,
            f"{len(preds)} emitted, {len(bad)} bad")
    c.done()
    return {"scored": scored, "batches": job.counters["batches"],
            "native_queue": gateway.native}


# -------------------------------------------------------------- kernel phase
def expected_site_counts(scorer, sizes: Sequence[int]) -> Dict[str, Dict]:
    """What the ops' own shape predicates say each site does for these
    dispatch sizes — computed here from ``ops`` alone, to hold against the
    scorer's dispatch/fallback counters."""
    from realtime_fraud_detection_tpu.ops import (
        epilogue_supported,
        flash_supported,
        matmul_supported,
        rows_supported,
    )
    from realtime_fraud_detection_tpu.scoring.pipeline import NUM_MODELS

    cfg, s = scorer.bert_config, scorer.sc.text_len
    h, ffn = cfg.hidden_size, cfg.intermediate_size
    disp = {"dequant_matmul": 0, "epilogue": 0, "attention": 0}
    fall = dict(disp)
    fused = flash_supported(s, cfg.head_dim, cfg.num_heads)
    for b in sizes:
        m = b * s
        for site in ("dequant_matmul", "epilogue"):
            disp[site] += 1
        # the attention site counts a launch once: fused core or reference
        (disp if fused else fall)["attention"] += 1
        if not (matmul_supported(m, h, h) and matmul_supported(m, h, ffn)
                and matmul_supported(m, ffn, h) and rows_supported(m, h)
                and rows_supported(s, h)):
            fall["dequant_matmul"] += 1
        if not epilogue_supported(b, NUM_MODELS):
            fall["epilogue"] += 1
    return {"dispatch": disp, "fallback": fall}


def expected_custom_calls(scorer, b: int) -> int:
    """Pallas call sites the kernels-on program for a ``b``-row batch holds
    per the same predicates: six dense sites and one attention per layer,
    the two embedding-row widens, the epilogue."""
    from realtime_fraud_detection_tpu.ops import (
        epilogue_supported,
        flash_supported,
        matmul_supported,
        rows_supported,
    )
    from realtime_fraud_detection_tpu.scoring.pipeline import NUM_MODELS

    cfg, s = scorer.bert_config, scorer.sc.text_len
    h, ffn, m = cfg.hidden_size, cfg.intermediate_size, b * s
    per_layer = (4 * matmul_supported(m, h, h) + matmul_supported(m, h, ffn)
                 + matmul_supported(m, ffn, h)
                 + flash_supported(s, cfg.head_dim, cfg.num_heads))
    return (cfg.num_layers * per_layer + rows_supported(m, h)
            + rows_supported(s, h) + epilogue_supported(b, NUM_MODELS))


def packed_call(scorer, batch, *, lower: bool = False, device=None):
    """The packed program ``dispatch_assembled`` launches, called directly
    on an assembled batch AT ITS OWN SHAPE (no bucket padding), with the
    scorer's own static selections. ``lower`` returns the lowering instead
    of running it; ``device`` runs it there with the parameters moved."""
    import jax

    from realtime_fraud_detection_tpu.core.packing import pack_tree
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        score_fused_packed,
    )

    blobs, spec = pack_tree(batch)
    mv = scorer.effective_model_valid()
    models, params = scorer.models, scorer.ensemble_params
    if device is not None:
        models, params = jax.device_put(
            jax.device_get((models, params)), device)
    fn = score_fused_packed.lower if lower else score_fused_packed
    with jax.default_device(device):
        out = fn(models, blobs["f32"], blobs["i32"], blobs["u8"], spec=spec,
                 params=params, model_valid=mv, blob_bf16=blobs["bf16"],
                 bert_config=scorer.bert_config,
                 use_pallas=scorer.effective_use_pallas(),
                 **scorer.quant_static(), **scorer.kernel_static())
    return out if lower else np.asarray(out)


def check_gemm_trees(c: Checks, x: np.ndarray, seed: int) -> None:
    """``QuantSettings.full()`` swaps the tree branches to their GEMM form,
    whose selected leaves must be the gather path's EXACTLY — on this
    device: a TPU's default matmul precision rounded the features and
    broke it (PR 21). The smoke's own trees are untrained (every row in
    one leaf), so a seeded ensemble split at this batch's own feature
    quantiles stands in."""
    import jax

    from realtime_fraud_detection_tpu.models.trees import (
        descend_complete_trees,
        gemm_leaf_index,
    )

    rng = np.random.default_rng(seed)
    n_trees, depth = 100, 6
    feature = rng.integers(
        0, x.shape[1], (n_trees, 2 ** depth - 1)).astype(np.int32)
    rank = (rng.uniform(0.05, 0.95, feature.shape)
            * (len(x) - 1)).astype(np.int64)
    threshold = np.sort(x, axis=0)[rank, feature].astype(np.float32)
    gather = np.asarray(jax.jit(descend_complete_trees)(
        feature, threshold, x))
    gemm = np.asarray(jax.jit(gemm_leaf_index)(feature, threshold, x))
    c.check("GEMM-form trees select the gather path's leaves",
            np.array_equal(gather, gemm),
            f"{int((gather != gemm).sum())} of {gather.size} differ, "
            f"{len(np.unique(gather))} distinct leaves in use")


def kernel_phase(size: SmokeSize, seed: int, expect_interpret: bool
                 ) -> Dict[str, Any]:
    """Quantized scorers, Pallas kernels off vs on, on identical assembled
    batches (kernel-drill's comparison, at this size)."""
    from realtime_fraud_detection_tpu.scoring.kernel_drill import (
        KernelDrillConfig,
        noise_floor,
    )

    c = Checks("kernels")
    gen = make_generator(size, seed)
    off = make_scorer(size, seed, gen, quant=True)
    on = make_scorer(size, seed, gen, quant=True, kernels=True)
    snap = on.kernel_snapshot()
    c.check(f"kernel_snapshot()['interpret'] is {expect_interpret}",
            snap["interpret"] is expect_interpret, snap["modes"])
    c.check("kernels-off scorer reports no kernel plane",
            off.kernel_snapshot()["interpret"] is False
            and not any(off.kernel_snapshot()["dispatch"].values()))
    worst, flips, tokens, last = 0.0, 0, [], None
    t0 = time.perf_counter()
    for b in size.kernel_buckets:
        recs = gen.generate_batch(b)
        # ONE assembly feeds both scorers: identical inputs by construction
        batch = off.assemble(recs)
        tokens.append((np.asarray(batch.token_ids),
                       np.asarray(batch.token_mask)))
        r_off = off.finalize(off.dispatch_assembled(batch, recs))
        r_on = on.finalize(on.dispatch_assembled(batch, recs))
        p_off = np.asarray([r["fraud_probability"] for r in r_off])
        p_on = np.asarray([r["fraud_probability"] for r in r_on])
        c.check(f"bucket {b}: kernels-on scores finite",
                all(_result_ok(r) for r in r_on))
        worst = max(worst, float(np.max(np.abs(p_off - p_on))))
        flips += sum(a["decision"] != z["decision"]
                     for a, z in zip(r_off, r_on))
        last = batch
    log(f"  [kernels] set-up: {len(size.kernel_buckets)} buckets x 2 "
        f"scorers first calls took {time.perf_counter() - t0:.1f} s")
    # kernel-drill's bound is ONE bf16 noise floor, which holds where the
    # interpreter replays the XLA ops (CPU: divergence ~5e-7). Compiled,
    # the two programs round independently: each sat within one floor of
    # true f32 on the v5e, so they may sit two apart (triangle inequality;
    # measured ratio 1.4, PR 21).
    bound = noise_floor(KernelDrillConfig(), off, tokens)["bound"] * (
        1.0 if expect_interpret else 2.0)
    c.check("kernels-on within kernel-drill's noise bound of kernels-off",
            worst <= bound, f"max |delta| {worst:.3e}, bound {bound:.3e}, "
                            f"{flips} decision flips")
    check_gemm_trees(c, np.asarray(last.features, np.float32), seed)
    snap = on.kernel_snapshot()
    want = expected_site_counts(on, size.kernel_buckets)
    log(f"  [kernels] site counts: dispatch {snap['dispatch']} "
        f"fallback {snap['fallback']}")
    c.check("site dispatch/fallback counts equal the shape predicates' "
            "prediction", snap["dispatch"] == want["dispatch"]
            and snap["fallback"] == want["fallback"], want)
    if not expect_interpret:
        # interpret mode inlines the kernel bodies; only a compiled
        # program holds Mosaic custom calls
        b = size.kernel_buckets[-1]
        text = packed_call(on, last, lower=True).compile().as_text()
        n_calls = text.count('custom_call_target="tpu_custom_call"')
        c.check(f"compiled bucket-{b} program contains tpu_custom_call",
                n_calls > 0, f"{n_calls} call sites")
        c.check("call sites equal the predicates' count",
                n_calls == expected_custom_calls(on, b),
                f"{n_calls} vs {expected_custom_calls(on, b)}")
    c.done()
    return {"max_divergence": worst, "bound": bound, "sites": snap}


# -------------------------------------------------------------- parity phase
def parity_phase(scorer, gen, size: SmokeSize) -> Dict[str, Any]:
    """The packed program on the scorer's device vs the SAME program on
    the host CPU backend of this process, one full bucket."""
    from realtime_fraud_detection_tpu.features.extract import host_cpu_device
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        MODEL_NAMES,
        OUT_COLUMNS,
    )

    c = Checks("parity")
    b = size.max_batch
    recs = gen.generate_batch(b)
    batch = scorer.assemble(recs)
    pending = scorer.dispatch_assembled(batch, recs)
    on_device = np.asarray(pending.out)
    scorer.finalize(pending)

    t0 = time.perf_counter()
    on_host = packed_call(scorer, batch, device=host_cpu_device())
    log(f"  [parity] host reference took {time.perf_counter() - t0:.1f} s "
        f"(compile included)")
    c.check("shape and finiteness",
            on_device.shape == on_host.shape
            == (b, len(OUT_COLUMNS) + len(MODEL_NAMES))
            and np.isfinite(on_device).all(), on_device.shape)
    cols = {name: j for j, name in enumerate(OUT_COLUMNS)}
    for name in ("fraud_probability", "confidence", "rule_score"):
        j = cols[name]
        d = float(np.max(np.abs(on_device[:, j] - on_host[:, j])))
        c.check(f"{name} within bf16 tolerance", d <= BF16_ATOL,
                f"max |delta| {d:.3e} <= {BF16_ATOL}")
    for j, name in enumerate(MODEL_NAMES, start=len(OUT_COLUMNS)):
        d = float(np.max(np.abs(on_device[:, j] - on_host[:, j])))
        c.check(f"branch {name} within bf16 tolerance", d <= BF16_ATOL,
                f"max |delta| {d:.3e}")
    j = cols["decision"]
    log(f"  [parity] decision flips across backends: "
        f"{int(np.sum(on_device[:, j] != on_host[:, j]))}/{b}")
    c.done()
    return {"batch": b}


# ----------------------------------------------------------- four-chip phase
def _drive(scorer, work: List[tuple], slots: int) -> List[np.ndarray]:
    """Dispatch pre-assembled batches keeping ``slots`` in flight,
    finalize in dispatch order; returns each batch's raw result matrix
    (the packed program's one output, real rows only)."""
    from collections import deque

    out: List[np.ndarray] = []
    inflight: deque = deque()

    def finish() -> None:
        pending = inflight.popleft()
        out.append(np.asarray(pending.out)[:pending.n])
        scorer.finalize(pending)

    for recs, batch in work:
        inflight.append(scorer.dispatch_assembled(batch, recs))
        while len(inflight) >= slots:
            finish()
    while inflight:
        finish()
    return out


def _compare(got: List[np.ndarray], want: List[np.ndarray]) -> Dict[str, Any]:
    """Bitwise row differences, the largest difference in any continuous
    column, and flips in the two ladder columns."""
    from realtime_fraud_detection_tpu.scoring.pipeline import OUT_COLUMNS

    g, w = np.concatenate(got), np.concatenate(want)
    ladder = [OUT_COLUMNS.index("decision"), OUT_COLUMNS.index("risk_level")]
    smooth = [j for j in range(g.shape[1]) if j not in ladder]
    return {"rows": len(g),
            "rows_differ": int((g != w).any(axis=1).sum()),
            "max_delta": float(np.abs(g[:, smooth] - w[:, smooth]).max()),
            "ladder_flips": int((g[:, ladder] != w[:, ladder]).sum())}


def _shard_shape_reference(single, batch, n_shards: int) -> np.ndarray:
    """Single-device scoring of each data shard's rows AT THE SHARD'S
    SHAPE (``rows / n_shards``-row slices through ``packed_call``)."""
    import jax

    per = batch.batch_size // n_shards
    return np.concatenate([
        packed_call(single, jax.tree.map(
            lambda a: np.asarray(a)[i * per:(i + 1) * per], batch))
        for i in range(n_shards)])


def multichip_phase(size: SmokeSize, seed: int, n_devices: int = 4
                    ) -> Dict[str, Any]:
    """DevicePool and MeshExecutor over ``n_devices`` against
    single-device scoring of the same assembled batches.

    What "the same scores" means was settled on four v5e chips (PR 21).
    The pool runs the identical program on each device: bit-identical.
    The mesh hands each device ``rows / data`` rows, and on the TPU the
    text branch's bf16 rounding depends on how many rows one program
    multiplies at once (a single device scoring 256 rows vs 4 x 64 differs
    the same way, max 7.5e-4 in the branch): so the mesh is bit-identical
    to single-device scoring of each shard's rows at the shard's shape —
    sharding adds nothing — and within bf16 tolerance of the full batch.
    """
    import jax

    from realtime_fraud_detection_tpu.scoring import DevicePool, MeshExecutor
    from realtime_fraud_detection_tpu.scoring.pipeline import (
        init_scoring_models,
    )

    c = Checks("multichip")
    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise PhaseFailed(f"multichip: {len(jax.devices())} devices, "
                          f"{n_devices} needed")
    gen = make_generator(size, seed)
    single = make_scorer(size, seed, gen, devices=devices[:1])
    models = single.models      # one init; every executor places its own
    # assemble ONCE (host side); every executor scores the same arrays, so
    # pipelined write-back order cannot change any executor's inputs
    sizes = [size.max_batch] * (2 * n_devices) + [size.max_batch // 2 - 3]
    work = []
    for n in sizes:
        recs = gen.generate_batch(n)
        work.append((recs, single.assemble(recs)))
    t0 = time.perf_counter()
    want = _drive(single, work, slots=1)
    log(f"  [multichip] single-device reference: {sum(map(len, want))} "
        f"scores, {time.perf_counter() - t0:.1f} s (compile included)")
    c.check("reference scores finite",
            [len(m) for m in want] == sizes
            and all(np.isfinite(m).all() for m in want))

    # ---- replicated pool
    scorer = make_scorer(size, seed, gen, devices=devices[:1], models=models)
    pool = DevicePool(scorer, devices=devices, inflight_depth=2)
    t0 = time.perf_counter()
    cmp_ = _compare(_drive(scorer, work, slots=pool.total_slots()), want)
    log(f"  [multichip] pool over {len(pool)} devices (donate="
        f"{pool.donate}): {time.perf_counter() - t0:.1f} s")
    c.check("pool scores bit-identical to single-device",
            cmp_["rows_differ"] == 0, cmp_)
    stats = pool.stats()
    log(f"  [multichip] pool per-device (dispatched, completed): "
        f"{[(d['device'], d['dispatched'], d['completed']) for d in stats['devices']]}")
    c.check("every pool device executed batches, none retried",
            all(d["completed"] > 0 and d["healthy"]
                for d in stats["devices"]) and stats["retries"] == 0)
    held = [{d for leaf in jax.tree_util.tree_leaves(rep.models)
             for d in leaf.devices()} for rep in pool.replicas]
    c.check("every pool device holds its own parameter replica",
            held == [{d} for d in devices], held)
    # hot swap across replicas: new weights reach every device
    fresh = init_scoring_models(
        jax.random.PRNGKey(seed + 1), bert_config=size.bert_config,
        feature_dim=scorer.sc.feature_dim, node_dim=scorer.sc.node_dim)
    swap_work = work[:n_devices]
    scorer.set_models(fresh)
    single.set_models(fresh)
    swapped = _drive(scorer, swap_work, slots=pool.total_slots())
    cmp_ = _compare(swapped, _drive(single, swap_work, slots=1))
    c.check("after hot swap every replica serves the new weights "
            "bit-identically", cmp_["rows_differ"] == 0
            and _compare(swapped, want[:n_devices])["rows_differ"] > 0, cmp_)
    single.set_models(models)

    # ---- GSPMD meshes: data-sharded, then data x model
    for model_axis in (1, 2):
        data_axis = n_devices // model_axis
        name = f"mesh {data_axis}x{model_axis}"
        scorer = make_scorer(size, seed, gen, devices=devices[:1],
                             models=models)
        ex = MeshExecutor(
            scorer, devices=devices, model_axis=model_axis,
            inflight_depth=2,
            shard_branches=("bert_text",) if model_axis > 1 else ())
        t0 = time.perf_counter()
        got = _drive(scorer, work, slots=ex.total_slots())
        log(f"  [multichip] {name} (donate={ex.donate}): "
            f"{time.perf_counter() - t0:.1f} s")
        cmp_ = _compare(got, want)
        c.check(f"{name} within bf16 tolerance of single-device scoring "
                f"of the whole batch", cmp_["max_delta"] <= BF16_ATOL, cmp_)
        cmp_ = _compare(got[:2], [_shard_shape_reference(
            single, batch, data_axis) for _, batch in work[:2]])
        c.check(f"{name} bit-identical to single-device scoring of each "
                f"data shard at the shard's shape",
                cmp_["rows_differ"] == 0, cmp_)
        leaf = ex.replicas[0].models.bert["layers"][0]["ffn1"]
        leaf = leaf.get("w", leaf.get("qw"))
        shards = leaf.addressable_shards
        c.check(f"{name}: a BERT leaf has a shard on every device",
                {s.device for s in shards} == set(devices),
                f"shard shape {shards[0].data.shape} of {leaf.shape}")
        pb = ex.param_bytes()["bert_text"]
        c.check(f"{name}: per-chip BERT bytes "
                f"{'below' if model_axis > 1 else 'equal to'} replicated",
                (pb["per_chip"] < pb["replicated"]) == (model_axis > 1), pb)
        st = ex.stats()
        c.check(f"{name}: every batch completed on the mesh",
                st["completed"] == len(work) and st["healthy"] == 1, st)
    c.done()
    return {"scores": sum(sizes)}


# ----------------------------------------------------------------------- main
def _cache_entries(path: str) -> int:
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except OSError:
        return 0


def run_phases(phases: List[Tuple[str, Callable[[], Any]]]) -> List[str]:
    failed = []
    for name, fn in phases:
        log(f"phase {name}: start")
        try:
            log(f"phase {name}: passed {fn()}")
        except Exception as e:  # noqa: BLE001 — boundary: run every phase
            if not isinstance(e, PhaseFailed):
                traceback.print_exc()
            log(f"phase {name}: FAILED — {type(e).__name__}: {e}")
            failed.append(name)
    return failed


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, population and traffic are made from it")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the pool/mesh path and its reference")
    args = ap.parse_args(argv)

    from realtime_fraud_detection_tpu.utils.chip import require_tpu
    from realtime_fraud_detection_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    cache_dir = configure_compile_cache()
    dev = require_tpu("chip_smoke.py")      # before anything else runs

    import importlib.metadata as md

    import jax

    from realtime_fraud_detection_tpu.native import (
        native_available,
        native_build_error,
    )

    hits = {"hits": 0, "misses": 0}

    def on_event(event: str, **_: Any) -> None:
        if event.endswith("/cache_hits"):
            hits["hits"] += 1
        elif event.endswith("/cache_misses"):
            hits["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    entries = _cache_entries(cache_dir)
    log(f"jax {jax.__version__}, jaxlib {md.version('jaxlib')}, "
        f"libtpu {md.version('libtpu')}; device {device}")
    log(f"compile cache: {cache_dir} "
        f"({'warm' if entries else 'cold'}: {entries} entries)")
    log(f"native build: available={native_available()} "
        f"error={native_build_error()}")

    size, seed = FULL, args.seed
    if args.chips == 4:
        phases = [("multichip", lambda: multichip_phase(size, seed, 4))]
    else:
        live: Dict[str, Any] = {}   # what set-up leaves for the phases

        def set_up() -> Dict[int, float]:
            live["gen"] = make_generator(size, seed)
            live["scorer"] = make_scorer(size, seed, live["gen"])
            return warm_buckets(live["scorer"], live["gen"], "set-up")

        phases = [
            ("set-up", set_up),
            ("serve", lambda: serve_phase(live["scorer"], live["gen"], size)),
            ("stream", lambda: stream_phase(live["scorer"], live["gen"],
                                            size)),
            ("kernels", lambda: kernel_phase(size, seed,
                                             expect_interpret=False)),
            ("parity", lambda: parity_phase(live["scorer"], live["gen"],
                                            size)),
        ]
    failed = run_phases(phases)

    log(f"compile cache: {hits['hits']} hits, {hits['misses']} misses this "
        f"run; {_cache_entries(cache_dir)} entries now")
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in jax.devices()]
    log(f"device memory peak_bytes_in_use per device: {peaks}")
    if failed:
        print(json.dumps({"ok": False, "failed": failed, "device": device}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
