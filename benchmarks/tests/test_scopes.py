"""The scope reduction (``harness/scopes.py``) on a hand-made event list and
on a recorded slice of the chip's trace, the wire-format reader on a
profile written here, and each new reader on a fake run. Arithmetic only:
no test reports a device number."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import scopes as S, spec
from benchmarks.harness import trace as T

FIXTURE = Path(__file__).parent / "fixtures" / "scope_events.json"
D = "/device:TPU:0"
H = "/host:CPU"
JIT = "jit(_score_fused_packed_impl)/"


def op(start, dur, op_name, plane=D, name="fusion"):
    return (plane, T.OPS_LINE, name, float(start), float(dur),
            S.scope_path(op_name))


def span(line, name, start, dur):
    return (H, line, name, float(start), float(dur), "")


def hand_made():
    """1 ms window. Device: text/layer0/ffn 100-300 us and (overlapping)
    250-350 us, text/layer0/attn_core 350-400, trees 400-450 inside an
    inner jit, an operation with no scope 450-460; idle 0-100, 460-1000.
    Host thread ``main``: job.dispatch_batch 0-90 holding assemble 10-80
    holding assemble.tokenize 20-70; job.complete_batch 470-900 holding
    device_wait 480-600 and job.fan_out 600-880; a collection 700-800 on
    another thread."""
    us = 1000.0
    return [
        span("main", "bench:slice", 0, 1000 * us),
        op(100 * us, 200 * us, JIT + "text/layer0/ffn/dot_general:"),
        op(250 * us, 100 * us, JIT + "text/layer0/ffn/add"),
        op(350 * us, 50 * us, JIT + "text/layer0/attn_core/reduce_sum"),
        op(400 * us, 50 * us,
           JIT + "trees/jit(tree_ensemble_predict)/jit(take)/gather:"),
        op(450 * us, 10 * us, "copy-done.1"),
        span("main", "rtfd:job.dispatch_batch", 0, 90 * us),
        span("main", "rtfd:assemble", 10 * us, 70 * us),
        span("main", "rtfd:assemble.tokenize", 20 * us, 50 * us),
        span("main", "rtfd:job.complete_batch", 470 * us, 430 * us),
        span("main", "rtfd:device_wait", 480 * us, 120 * us),
        span("main", "rtfd:job.fan_out", 600 * us, 280 * us),
        span("server", "rtfd:host.gc", 700 * us, 100 * us),
        span("main", "bench:job.complete_batch", 465 * us, 440 * us),
    ]


@pytest.mark.parametrize("op_name,path", [
    (JIT + "text/layer3/ffn/dot_general:", "text/layer3/ffn"),
    (JIT + "text/layer11/attn_core/bhqd,bhkd->bhqk/dot_general:",
     "text/layer11/attn_core"),
    (JIT + "text/layer0/softmax_helper/exp", "text/layer0"),
    (JIT + "text/embed/gather:", "text/embed"),
    (JIT + "text/head/dot_general", "text/head"),
    (JIT + "text/reduce_sum", "text"),
    (JIT + "iforest/jit(iforest_predict)/jit(take_along_axis)/gather:",
     "iforest"),
    ("jit(f)/jit(g)/repack/concatenate", "repack"),
    ("copy-done.1", ""),
    (JIT + "ffn/dot_general", ""),          # a kernel name outside text
    ("", ""),
])
def test_scope_path_of_an_op_name(op_name, path):
    assert S.scope_path(op_name) == path


# another architecture's program: a second sequence branch, and experts
# nested two levels below a layer
MOE = {"text": {"embed": {}, "layer*": {
           "attn_core": {}, "router": {},
           "experts": {"expert*": {"up": {}, "down": {}}}}},
       "events": {"block*": {"scan": {}}}, "trees": {}}


@pytest.mark.parametrize("op_name,path", [
    (JIT + "text/layer3/experts/expert17/down/dot_general:",
     "text/layer3/experts/expert17/down"),
    (JIT + "text/layer3/experts/expert17/silu/mul",
     "text/layer3/experts/expert17"),
    (JIT + "text/layer3/experts/jit(grouped)/expert2/up/dot_general",
     "text/layer3/experts/expert2/up"),
    (JIT + "text/layer3/router/top_k", "text/layer3/router"),
    (JIT + "text/layer3/ffn/dot_general", "text/layer3"),   # not its part
    (JIT + "text/head/dot_general", "text"),
    (JIT + "events/block39/scan/while", "events/block39/scan"),
    (JIT + "events/blockx/scan", "events"),
    (JIT + "lstm/while", ""),                # no branch of THIS vocabulary
    (JIT + "expert3/up/dot_general", ""),
])
def test_scope_path_keeps_every_declared_part_at_any_depth(op_name, path):
    assert S.scope_path(op_name, MOE) == path


def test_a_deeper_vocabulary_reduces_to_deeper_paths():
    us = 1000.0
    events = [
        span("main", "bench:slice", 0, 1000 * us),
        (D, T.OPS_LINE, "fusion", 100 * us, 100 * us, S.scope_path(
            JIT + "text/layer0/experts/expert1/up/dot_general", MOE)),
        (D, T.OPS_LINE, "fusion", 200 * us, 50 * us, S.scope_path(
            JIT + "text/layer0/experts/expert2/up/dot_general", MOE)),
        (D, T.OPS_LINE, "fusion", 300 * us, 25 * us, S.scope_path(
            JIT + "lstm/while", MOE)),
    ]
    s = S.reduce(events)["scope_s"]
    assert s["text/layer0/experts"] == pytest.approx(150e-6)
    assert s["text/layer0/experts/expert2/up"] == pytest.approx(50e-6)
    assert S.matching(s, "text/layer*/experts/expert*/up") == \
        pytest.approx(150e-6)
    assert s[S.UNSCOPED] == pytest.approx(25e-6)


def _names(vocabulary):
    for name, below in vocabulary.items():
        yield name
        yield from _names(below)


@pytest.mark.parametrize("builder", sorted(
    p.stem for p in (spec.BENCH / "configs").glob("*_builder.py")))
def test_a_builders_vocabulary_is_names_the_program_writes(builder):
    """Subset, not equality: the program may name more than a builder
    reads. (What tier-1 ``tests/test_scopes.py`` should hold per builder,
    in place of its equality with the ensemble's tuples.)"""
    from realtime_fraud_detection_tpu.obs import scopes as program

    written = {v for v in vars(program).values() if isinstance(v, str)}
    written |= set(program.BRANCH_SCOPES) | set(program.LAYER_SCOPES)
    vocabulary = spec.builder({"builder": builder}).VOCABULARY
    assert set(vocabulary) <= set(program.BRANCH_SCOPES)
    for name in _names(vocabulary):
        assert name.replace("*", "") in written, name
    assert S.digits_re(S.LAYER).match(program.layer_scope(11))


def test_the_default_vocabulary_is_the_ensembles_tuples():
    v = spec.builder({}).VOCABULARY
    assert v is S.ENSEMBLE_VOCABULARY
    assert tuple(v) == S.BRANCHES
    assert set(v[S.TEXT]) == set(S.TEXT_PARTS) | {S.LAYER}
    assert tuple(v[S.TEXT][S.LAYER]) == S.LAYER_PARTS
    assert all(not v[b] for b in S.BRANCHES if b != S.TEXT)


def test_scope_sums_are_unions_and_parents_hold_children():
    r = S.reduce(hand_made())
    s = r["scope_s"]
    assert s["text/layer0/ffn"] == pytest.approx(250e-6)     # union
    assert s["text/layer0/attn_core"] == pytest.approx(50e-6)
    assert s["text/layer0"] == s["text"] == pytest.approx(300e-6)
    assert s["trees"] == pytest.approx(50e-6)
    assert s[S.UNSCOPED] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(360e-6)
    assert r["scoped"] is True
    assert s["text"] + s["trees"] + s[S.UNSCOPED] == pytest.approx(
        r["busy_s"])
    assert S.matching(s, "text/layer*/ffn") == pytest.approx(250e-6)
    assert S.matching(s, "text") == pytest.approx(300e-6)
    # a scope with no operation is None, never 0
    assert S.matching(s, "gnn") is None
    assert S.matching(s, "text/layer*/ln") is None


def test_operations_are_clipped_to_the_window():
    r = S.reduce(hand_made(), window=(150e3, 425e3))
    assert r["scope_s"]["text/layer0/ffn"] == pytest.approx(200e-6)
    assert r["scope_s"]["trees"] == pytest.approx(25e-6)
    assert S.UNSCOPED not in r["scope_s"]


def test_host_spans_get_self_times_by_nesting_on_their_thread():
    h = S.reduce(hand_made())["host_spans"]
    assert h["job.dispatch_batch"] == {
        "count": 1, "total_s": pytest.approx(90e-6),
        "self_s": pytest.approx(20e-6)}
    assert h["assemble"]["self_s"] == pytest.approx(20e-6)
    assert h["assemble.tokenize"]["self_s"] == pytest.approx(50e-6)
    assert h["job.complete_batch"]["self_s"] == pytest.approx(30e-6)
    # the collection ran on another thread: it is nobody's child
    assert h["job.fan_out"]["self_s"] == pytest.approx(280e-6)
    assert h["host.gc"]["total_s"] == pytest.approx(100e-6)
    assert "job.complete_batch" in h and "slice" not in h   # bench: left


def test_gaps_go_to_the_innermost_span_covering_most_of_them():
    events = hand_made()
    r = S.reduce(events)
    gaps = dict(r["idle_gaps"])
    # 0-100 us: dispatch_batch > assemble > assemble.tokenize (50 of 100)
    assert gaps["rtfd:assemble.tokenize"] == pytest.approx(100e-6)
    # 460-1000 us: complete_batch covers 430 of 540, fan_out 280 (most
    # of it, and deeper); the collection covers only 100
    assert gaps["rtfd:job.fan_out"] == pytest.approx(540e-6)
    assert r["gap_s"] == pytest.approx(640e-6)
    assert r["gap_uncovered_s"] == 0.0
    # a collection that covers most of a gap takes it from any span
    events.append(op(470e3, 225e3, JIT + "text/head/dot_general"))
    events.append(op(805e3, 195e3, JIT + "text/head/dot_general"))
    gaps = dict(S.reduce(events)["idle_gaps"])
    assert gaps["rtfd:host.gc"] == pytest.approx(110e-6)     # 695-805
    # with the program's spans gone, every gap is uncovered
    bare = [e for e in hand_made() if not e[2].startswith(S.PREFIX)]
    r = S.reduce(bare)
    assert r["gap_uncovered_s"] == r["gap_s"] == pytest.approx(640e-6)
    assert r["host_spans"] == {}


def test_a_program_without_scopes_and_a_trace_without_a_device():
    no_scope = [(p, l, n, a, d, "") for p, l, n, a, d, _ in hand_made()]
    r = S.reduce(no_scope)
    assert r["scoped"] is False
    assert r["scope_s"] == {S.UNSCOPED: pytest.approx(360e-6)}
    assert S.reduce([e for e in hand_made() if e[0] != D]) is None
    with pytest.raises(ValueError, match="bench:slice"):
        S.reduce([e for e in hand_made() if e[2] != "bench:slice"])


def test_several_devices_are_summed():
    events = hand_made() + [op(0, 1000e3, JIT + "gnn/dot_general",
                               plane="/device:TPU:1")]
    r = S.reduce(events)
    assert r["busy_s"] == pytest.approx(360e-6 + 1000e-6)
    assert r["scope_s"]["gnn"] == pytest.approx(1000e-6)


def test_recorded_fixture():
    """The first 250 ms of the chip's traced slice (``s512-longtail-
    saturated``, PR 23) as ``read_xplane`` flattened it — the device waits
    for the first batch's assembly, then runs one program and the start of
    the next: the books close, the names are the program's, and the gap
    goes to one of the program's own spans."""
    assert FIXTURE.stat().st_size < 200 * 1024
    rec = json.loads(FIXTURE.read_text())
    events = [tuple(e) for e in rec["events"]]
    r = S.reduce(events, window=tuple(rec["window"]), top=10 ** 6)
    s = r["scope_s"]
    tops = {p: v for p, v in s.items() if "/" not in p}
    # scopes overlap only where an operation encloses others (the LSTM's
    # ``while`` carries no scope, the steps inside it do)
    assert r["busy_s"] <= sum(tops.values()) < 1.001 * r["busy_s"]
    assert set(tops) >= {"text", "trees", "iforest", "lstm", "gnn",
                         S.UNSCOPED}
    assert s[S.UNSCOPED] < 0.01 * r["busy_s"]
    layers = {p for p in s if p.count("/") == 2}
    assert layers == {f"text/layer{i}/{k}" for i in range(6)
                      for k in S.LAYER_PARTS}
    assert S.matching(s, "text/layer*/attn_core") > S.matching(
        s, "text/layer*/ffn") > S.matching(s, "text/layer*/ln")
    assert sum(S.matching(s, f"text/layer*/{k}") for k in S.LAYER_PARTS) \
        + s["text/embed"] + s["text/head"] == pytest.approx(s["text"],
                                                            rel=1e-3)
    assert sum(g for _, g in r["idle_gaps"]) == pytest.approx(r["gap_s"])
    assert r["gap_uncovered_s"] < 0.05 * r["gap_s"]
    assert r["idle_gaps"][0][0].startswith(S.PREFIX)
    assert {"job.dispatch_batch", "assemble", "assemble.tokenize", "pack",
            "dispatch", "job.complete_batch", "device_wait",
            "job.fan_out"} <= set(r["host_spans"])
    h = r["host_spans"]
    assert h["assemble"]["self_s"] < h["assemble"]["total_s"]


def test_op_names_from_a_profile_written_here(tmp_path):
    """The wire-format reader against ``ProfileData`` on a real file: the
    CPU backend writes no ``tf_op`` and no device plane, so every map is
    empty, and ``read_xplane`` still returns the host's ``rtfd:`` spans."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench:slice"):
            with jax.profiler.TraceAnnotation("rtfd:assemble", batch=3):
                jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = T.newest_xplane(str(tmp_path))
    assert S.op_names(path) == {}
    events = S.read_xplane(path)
    assert {e[2] for e in events} == {"bench:slice", "rtfd:assemble"}
    assert S.reduce(events) is None          # no device operation


def test_wire_format_reader_on_a_hand_encoded_plane(tmp_path):
    """XSpace{planes[1]: XPlane{name 2, event_metadata 4, stat_metadata
    5}} encoded by hand: a ``tf_op`` given as a string and one given as a
    reference to a stat's name."""
    def varint(n):
        out = bytearray()
        while True:
            b, n = n & 0x7F, n >> 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)

    def ld(field, payload):
        return varint(field << 3 | 2) + varint(len(payload)) + payload

    def vi(field, n):
        return varint(field << 3) + varint(n)

    def stat_meta(sid, name):
        return ld(5, vi(1, sid) + ld(2, vi(1, sid) + ld(2, name)))

    def event_meta(eid, name, stat):
        return ld(4, vi(1, eid) + ld(2, vi(1, eid) + ld(2, name)
                                     + ld(5, stat)))

    plane = (ld(2, b"/device:TPU:0")
             + stat_meta(26, b"tf_op") + stat_meta(300, b"other")
             + stat_meta(77, JIT.encode() + b"trees/gather:")
             + event_meta(1, b"%fusion.1 = f32[8] fusion(...)",
                          vi(1, 26) + ld(5, JIT.encode()
                                         + b"text/layer0/ffn/dot_general:"))
             + event_meta(2, b"%fusion.2", vi(1, 26) + vi(7, 77))
             + event_meta(3, b"%copy-done.1", vi(1, 300) + ld(5, b"x")))
    host = ld(2, b"/host:CPU") + stat_meta(26, b"tf_op") \
        + event_meta(1, b"rtfd:assemble", vi(1, 26) + ld(5, b"text/x"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ld(1, plane) + ld(1, host))
    assert S.op_names(str(path)) == {D: {
        "%fusion.1 = f32[8] fusion(...)":
            JIT + "text/layer0/ffn/dot_general:",
        "%fusion.2": JIT + "trees/gather:"}}


# ---- the readers, each on a fake run

CFG = {"n_layers": 6, "dim": 768, "hidden_dim": 3072, "n_heads": 12}


def fake_run(**kw):
    base = dict(
        trace={"window_s": 1.0}, counters_slice={
            "batches": 2, "scored": 512, "token_slots": 2 * 256 * 512,
            "token_slots_sq": 2 * 256 * 512 * 512},
        counters={"scored": 1000, "batches": 4, "token_slots": 4 * 256 * 512,
                  "token_slots_sq": 4 * 256 * 512 ** 2,
                  "real_tokens": 4 * 256 * 64},
        stages={"assemble.tokenize": {"total_s": 0.030},
                "job.complete_batch": {"total_s": 2.5},
                "device_wait": {"total_s": 2.4},
                "job.poll": {"total_s": 0.1}},
        t_open=100.0, t_count_snap=103.0,
        extra={"cfg": CFG, "device": {"kind": "TPU v5 lite"},
               "scope_trace": {
                   "busy_s": 0.40, "scoped": True,
                   "scope_s": {"text": 0.36, "text/layer0": 0.18,
                               "text/layer1": 0.18,
                               "text/layer0/ffn": 0.04,
                               "text/layer1/ffn": 0.04,
                               "text/layer0/attn_core": 0.09,
                               "text/layer1/attn_core": 0.09,
                               "trees": 0.01, "iforest": 0.012,
                               "lstm": 0.001, "gnn": 0.001,
                               "repack": 0.0005, S.UNSCOPED: 0.004}}})
    base.update(kw)
    return types.SimpleNamespace(**base)


def metric(name, run):
    return spec.reader_for(name, "per_layer")(run)


def test_scope_time_readers():
    run = fake_run()
    assert metric("text_ms_per_batch", run) == pytest.approx(180.0)
    assert metric("ffn_ms_per_batch", run) == pytest.approx(40.0)
    assert metric("attn_core_ms_per_batch", run) == pytest.approx(90.0)
    # the four branches must be there; glue counts where it exists
    assert metric("nontext_ms_per_batch", run) == pytest.approx(
        1e3 * (0.01 + 0.012 + 0.001 + 0.001 + 0.0005) / 2)
    assert metric("unscoped_device_pct", run) == pytest.approx(1.0)


def test_a_scope_with_no_operation_leaves_the_metric_out(capsys):
    run = fake_run()
    del run.extra["scope_trace"]["scope_s"]["gnn"]
    assert metric("nontext_ms_per_batch", run) is None
    assert "no device operation under scope 'gnn'" in capsys.readouterr().out
    assert metric("text_ms_per_batch", run) == pytest.approx(180.0)


def test_roofline_readers_charge_the_flops_the_program_counted():
    run = fake_run()
    ffn = spec.kernel("ffn").flops(2 * 256 * 512, dim=768, hidden_dim=3072,
                                   layers=6)
    assert ffn == 2 * 2 * 2 * 256 * 512 * 768 * 3072 * 6
    assert metric("ffn_roofline_pct", run) == pytest.approx(
        100 * ffn / 197e12 / 0.08)
    attn = spec.kernel("attn_core").flops(2 * 256 * 512 ** 2, heads=12,
                                          head_dim=64, layers=6)
    assert attn == 2 * 2 * 12 * 2 * 256 * 512 ** 2 * 64 * 6
    assert metric("attn_core_roofline_pct", run) == pytest.approx(
        100 * attn / 197e12 / 0.18)
    # half the text length launched: a quarter of attention's FLOPs, half
    # of the FFN's — from the counters, whatever the configuration says
    run.counters_slice.update(token_slots=2 * 256 * 256,
                              token_slots_sq=2 * 256 * 256 ** 2)
    assert metric("ffn_roofline_pct", run) == pytest.approx(
        50 * ffn / 197e12 / 0.08)
    assert metric("attn_core_roofline_pct", run) == pytest.approx(
        25 * attn / 197e12 / 0.18)
    # a program that does not count its tokens reports no share
    for key in ("token_slots", "token_slots_sq"):
        del run.counters_slice[key]
    assert metric("ffn_roofline_pct", run) is None
    assert metric("attn_core_roofline_pct", run) is None


def test_a_kernel_without_a_file_stops_with_the_path_looked_for():
    read = spec._load_module(spec.BENCH / "readers" / "scope_roofline.py",
                             "reader").read
    with pytest.raises(SystemExit,
                       match="no file benchmarks/kernels/softmax.py"):
        read(fake_run(), scope="text/layer*/ffn", kernel="softmax")


def test_a_metric_files_kernel_resolves_before_any_work(tmp_path,
                                                        monkeypatch):
    bench = tmp_path / "benchmarks"
    (bench / "layer_metrics").mkdir(parents=True)
    (bench / "layer_metrics" / "softmax_roofline_pct.json").write_text(
        json.dumps({"reader": "scope_roofline", "args": {
            "scope": "text/layer*/attn_core", "kernel": "softmax"}}))
    (bench / "readers").symlink_to(spec.BENCH / "readers")
    monkeypatch.setattr(spec, "BENCH", bench)
    monkeypatch.setattr(spec, "ROOT", tmp_path)
    with pytest.raises(SystemExit,
                       match="no file benchmarks/kernels/softmax.py"):
        spec.reader_for("softmax_roofline_pct", "per_layer")


def test_a_byte_bound_kernel_reports_against_the_bandwidth_roofline():
    read = spec._load_module(spec.BENCH / "readers" / "scope_roofline.py",
                             "reader").read
    run = fake_run()
    needs = spec.kernel("attn_core").work(run.counters_slice, CFG)
    # q, k, v read and the context written, bf16, six layers
    assert needs["hbm_bytes"] == 4 * 2 * 256 * 512 * 768 * 2 * 6
    assert read(run, scope="text/layer*/attn_core", kernel="attn_core",
                peak="hbm_bytes_per_s") == pytest.approx(
        100 * needs["hbm_bytes"] / 819e9 / 0.18)
    assert read(run, scope="text/layer*/attn_core", kernel="attn_core",
                peak="int8_ops_per_s") == pytest.approx(
        100 * needs["flops"] / 393e12 / 0.18)
    ffn = spec.kernel("ffn").work(run.counters_slice, CFG)
    assert ffn["hbm_bytes"] == 6 * (2 * 2 * 256 * 512 * 768 * 2
                                    + 2 * 2 * 768 * 3072 * 4)
    with pytest.raises(ValueError, match="no published"):
        read(run, scope="text/layer*/ffn", kernel="ffn", peak="fp4_per_s")


def test_counter_and_span_readers():
    run = fake_run()
    assert metric("token_padding_pct", run) == pytest.approx(87.5)
    assert metric("tokenize_us_per_txn", run) == pytest.approx(30.0)
    assert metric("complete_self_us_per_txn", run) == pytest.approx(100.0)
    assert metric("job_thread_busy_pct", run) == pytest.approx(
        100 * (3.0 - 2.5) / 3.0)


def test_every_new_reader_returns_none_on_a_program_without_the_names():
    """The parent of PR 23: no scope, no ``rtfd:`` span, no token counter,
    only the five stage names of PR 22."""
    old = fake_run(
        counters_slice={"batches": 2, "scored": 512},
        counters={"scored": 1000, "batches": 4},
        stages={k: {"total_s": 1.0} for k in (
            "assemble", "graph", "pack", "dispatch", "device_wait")})
    old.extra["scope_trace"] = None          # what for_run leaves there
    bm = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    new = [m["name"] for m in bm["per_layer"]][7:]
    assert len(new) == 11
    for name in new:
        assert metric(name, old) is None, name
    untraced = fake_run(trace=None)
    del untraced.extra["scope_trace"]
    assert metric("text_ms_per_batch", untraced) is None


def test_for_run_says_once_why_it_found_nothing(capsys, monkeypatch):
    run = fake_run()
    del run.extra["scope_trace"]
    no_scope = [(p, l, n, a, d, "") for p, l, n, a, d, _ in hand_made()]
    monkeypatch.setattr(T, "newest_xplane", lambda d: "x")
    monkeypatch.setattr(S, "read_xplane", lambda p, vocabulary: no_scope)
    assert S.for_run(run) is None and S.for_run(run) is None
    out = capsys.readouterr().out
    assert out.count("no device operation carries a named scope") == 1
    assert out.count("idle gaps by the program's own spans") == 1


def test_traced_rehearsal_reads_the_program_spans_and_leaves_the_device_out(
        tmp_path):
    """A traced TINY cell on the CPU, through the new readers: the ones that
    read the program's spans and counters report, the ``device_trace`` ones
    find no device operation, say so, and are left out."""
    import rehearsal
    from test_rehearsal import run_cell

    copy = rehearsal.make_tiny_copy(tmp_path)
    out, log = run_cell(copy, "s512-fulltext-saturated", trace=1)
    assert out["correct"] is True
    m = out["metrics"]
    assert {"token_padding_pct", "tokenize_us_per_txn",
            "complete_self_us_per_txn", "job_thread_busy_pct"} <= set(m)
    assert 0.0 <= m["token_padding_pct"]["value"] < 100.0
    assert 0.0 < m["job_thread_busy_pct"]["value"] <= 100.0
    assert m["tokenize_us_per_txn"]["value"] > 0.0
    assert "scopes: the trace has no device operation" in log
    for name in ("text_ms_per_batch", "nontext_ms_per_batch",
                 "attn_core_ms_per_batch", "ffn_ms_per_batch",
                 "attn_core_roofline_pct", "ffn_roofline_pct",
                 "unscoped_device_pct"):
        assert name not in m
        assert f"metric {name}: nothing to read, left out" in log
    assert "'assemble.tokenize'" in log and "'job.fan_out'" in log
