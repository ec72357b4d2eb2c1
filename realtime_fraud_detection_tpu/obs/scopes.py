"""The names the program gives its own work, in one place.

Device side: ``jax.named_scope`` names on the fused scoring program's
branches and on the text branch's kernels. They are HLO metadata only
(``op_name`` of each instruction; the compiled program is the same with
and without them, ``tests/test_scopes.py``) and reach a profiler trace as
the operation's name path, ``jit(...)/text/layer0/ffn/dot_general``.

Host side: the ``SpanTimer`` span names of one microbatch, outermost
first, as ``FraudScorer`` and ``StreamJob`` open them
(``obs/profiling.SpanTimer.span``). In a profiler session each is a
``TraceAnnotation`` named ``rtfd:<name>`` carrying ``batch=<n>``.

``benchmarks/harness/scopes.py`` matches on these strings; ``PERF.md`` §3
says which metric reads which.

Parts: four of the layer scopes are cut once more, by a ``named_scope``
nested INSIDE the scope (``SCOPE_PARTS``: ``ssm_proj`` -> ``in_proj`` /
``gate_norm`` / ``out_proj``, ``delta_conv`` -> ``qk_norm`` / ``gates``,
``router`` -> ``choose`` / ``order``, Falcon-H1's ``ffn`` -> ``gate`` / ``up``
/ ``down``). An operation's path grows by one component
(``text/layer3/ssm_proj/in_proj/dot_general``), so whatever reads the parent
reads what it read; a part's own metric is
``benchmarks/readers/scope_part_time_per_batch.py``. A part never holds a
custom call: a kernel's ``op_name`` is spelt by tests, kernels' files and
documents (``.../delta_conv/jit(_conv_pallas)/causal_conv/pallas_call``) and
stays directly under its scope. A part exists where a queued issue needs
the number, not wherever one could be cut. To add one: a name in
``SCOPE_PARTS``, a ``named_scope`` in the model's file, a
``benchmarks/layer_metrics/<metric>.json`` naming ``scope`` and ``part``, a
``per_layer`` entry of ``BENCHMARK.json`` with its ``workloads``, a row of
``PERF.md`` §3 (``docs/tracing.md`` "Parts of a scope").
"""

from __future__ import annotations

from typing import Dict, Tuple

# ---- device: branches of scoring/pipeline._score_fused_impl
TREES = "trees"
LSTM = "lstm"
TEXT = "text"
GNN = "gnn"
IFOREST = "iforest"
RULES = "rules"
BLEND = "blend"
# ---- device: the packed entry's own work (_score_fused_packed_impl)
UNPACK = "unpack"
REPACK = "repack"
BRANCH_SCOPES: Tuple[str, ...] = (TREES, LSTM, TEXT, GNN, IFOREST, RULES,
                                  BLEND, UNPACK, REPACK)

# ---- device: under ``text`` (models/bert.py)
EMBED = "embed"
LAYER = "layer"              # ``layer<i>``, i from 0
ATTN_PROJ = "attn_proj"      # q, k, v and o projections
ATTN_CORE = "attn_core"      # scores, mask, softmax, weighted sum
FFN = "ffn"                  # ffn1, GELU, ffn2
LN = "ln"                    # both layer norms with their residual adds
HEAD = "head"
LAYER_SCOPES: Tuple[str, ...] = (ATTN_PROJ, ATTN_CORE, FFN, LN)

# ---- device: under ``text`` where the encoder is models/olmoe.py. ``embed``,
# ``head``, ``layer<i>`` and ``attn_core`` as above; ``attn_proj`` also holds
# QK-norm and RoPE, ``ln`` the RMSNorms and residual adds; the sparse block
# stands where ``ffn`` does
ROUTER = "router"            # gate matmul, softmax, top-k, sort and offsets
ROUTER_CHOOSE = "choose"     # part: the encoder's own choice (gate matmul,
                             # softmax / sigmoid, top-k; ZAYA1's router MLP
                             # and the state it carries)
ROUTER_ORDER = "order"       # part: the pairs' sort, group sizes, inverse
                             # permutation and the fused kernel's tile count
EXPERTS = "experts"
EXPERTS_DISPATCH_PART = "dispatch"   # rows gathered into expert order
EXPERTS_MATMUL_PART = "matmul"       # grouped gate, up, SiLU*, down
EXPERTS_COMBINE_PART = "combine"     # rows home, weighted, summed
EXPERTS_PARTS: Tuple[str, ...] = (EXPERTS_DISPATCH_PART, EXPERTS_MATMUL_PART,
                                  EXPERTS_COMBINE_PART)
EXPERTS_DISPATCH, EXPERTS_MATMUL, EXPERTS_COMBINE = (
    f"{EXPERTS}/{part}" for part in EXPERTS_PARTS)
MOE_LAYER_SCOPES: Tuple[str, ...] = (ATTN_PROJ, ATTN_CORE, ROUTER, EXPERTS,
                                     LN)

# ---- device: under ``text`` where the encoder is models/zaya.py: the MoE
# names above (``attn_proj`` the four projections alone; ``router`` also
# holds the router's down-projection, its state carry and its MLP) and
ATTN_MIX = "attn_mix"        # between the latent projections and the core:
                             # both convolutions, q-k mean, L2 norms and
                             # temperature, RoPE, the value shift
ZAYA_LAYER_SCOPES: Tuple[str, ...] = MOE_LAYER_SCOPES + (ATTN_MIX,)


# ---- device: under ``text`` where the encoder is models/laguna.py: the MoE
# names above (``attn_proj`` holds q, k, v, the head gate, RoPE and o;
# ``attn_core`` the windowed or full causal core; layer 0's dense MLP is
# ``ffn``, the name DistilBERT's uses) and
SHARED_EXPERT = "shared_expert"   # the expert every token passes through
LAGUNA_LAYER_SCOPES: Tuple[str, ...] = MOE_LAYER_SCOPES + (FFN,
                                                           SHARED_EXPERT)

# ---- device: under ``text`` where the encoder is models/joyai.py: Laguna's
# names (``attn_proj`` holds the two up-projections out of the latents, by
# part, and o; ``attn_core`` the latent causal core) and
ATTN_LATENT = "attn_latent"  # what latent attention puts in front of the
                             # projections: the two down-projections into
                             # the query and key-value latents, their
                             # RMSNorms, the shared key's split
JOYAI_LAYER_SCOPES: Tuple[str, ...] = LAGUNA_LAYER_SCOPES + (ATTN_LATENT,)

# ---- device: under ``text`` where the encoder is models/falcon_h1.py: a
# causal DENSE encoder (``attn_proj`` holds q, k with its multiplier, v and
# o; ``attn_core`` Laguna's causal core with no window and no gate; ``ffn``
# the SwiGLU MLP with its two multipliers; ``ln`` both RMSNorms and the
# residual adds, the first of which sums the two mixers) and the Mamba-2
# mixer that runs beside attention in every layer
SSM_PROJ = "ssm_proj"        # W_in with the µP vector, the gate, the
                             # grouped RMSNorm, W_out
SSM_IN_PROJ = "in_proj"      # part: W_in with its multipliers, the cuts of
                             # z and dt from its result
SSM_GATE_NORM = "gate_norm"  # part: y * SiLU(z) and the grouped RMSNorm
SSM_OUT_PROJ = "out_proj"    # part: W_out
SSM_CONV = "ssm_conv"        # the depthwise causal convolution, its SiLU,
                             # dt's softplus
SSM_SCAN = "ssm_scan"        # the state-space scan alone (ops/ssd_scan.py)
FALCON_H1_LAYER_SCOPES: Tuple[str, ...] = LAYER_SCOPES + (
    SSM_PROJ, SSM_CONV, SSM_SCAN)
# parts of Falcon-H1's ``ffn`` alone (DistilBERT's, Laguna's and JoyAI's are
# left whole): one matmul each; ``gate`` holds its multiplier, ``up`` the
# SiLU, the product and the rounding to what ``down`` reads, where the TPU's
# compiler fuses them
FFN_GATE = "gate"
FFN_UP = "up"
FFN_DOWN = "down"

# ---- device: under ``text`` where the encoder is models/nemotron_h.py: a
# layer is ONE mixer, so ``layer<i>`` holds ``ln`` (its one RMSNorm and the
# residual add) and the scopes of its KIND alone — a Mamba-2 layer
# ``ssm_proj`` / ``ssm_conv`` / ``ssm_scan`` (Falcon-H1's mixer, without
# multipliers), an attention layer ``attn_proj`` (q, k, v, o: no rotation)
# and ``attn_core``, a routed layer ``router`` / ``experts`` /
# ``shared_expert`` (experts without a gate: ``experts/matmul`` is up,
# relu^2 and down)
NEMOTRON_H_LAYER_SCOPES: Tuple[str, ...] = (
    LN, SSM_PROJ, SSM_CONV, SSM_SCAN, ATTN_PROJ, ATTN_CORE, ROUTER, EXPERTS,
    SHARED_EXPERT)

# ---- device: under ``text`` where the encoder is models/qwen3_next.py:
# every layer holds ``ln`` (both zero-centred RMSNorms and the residual
# adds), ``router`` / ``experts`` / ``shared_expert`` (the shared one under
# its scalar gate) and the scopes of its mixer's KIND — a softmax-attention
# layer ``attn_proj`` (q with its gate, k, v, o; in the XLA form also the
# per-head norms and the rotation) and ``attn_core``, a Gated-DeltaNet layer
DELTA_PROJ = "delta_proj"    # in_proj_qkvz and in_proj_ba by part, the
                             # gated per-head RMSNorm, out_proj
DELTA_CONV = "delta_conv"    # the depthwise causal convolution, its SiLU,
                             # q's and k's L2 norms, beta and the log-decay
DELTA_QK_NORM = "qk_norm"    # part of ``delta_conv``: q's and k's L2 norms,
                             # q's ``dk ** -0.5``, the reshapes round them
DELTA_GATES = "gates"        # part of ``delta_conv``: beta and the log-decay
                             # g; the convolution (and v's reshape to
                             # heads) stays directly under ``delta_conv``
DELTA_SCAN = "delta_scan"    # the delta-rule scan alone (ops/delta_scan.py)
QWEN3_NEXT_LAYER_SCOPES: Tuple[str, ...] = (
    LN, DELTA_PROJ, DELTA_CONV, DELTA_SCAN, ATTN_PROJ, ATTN_CORE, ROUTER,
    EXPERTS, SHARED_EXPERT)

# ---- device: the parts written INSIDE a layer scope (module docstring).
# Under ``ssm_proj``, ``router`` and Falcon-H1's ``ffn`` every operation lies
# in exactly one part; under ``delta_conv`` what lies in none is the
# convolution (tests/test_scopes.py)
SCOPE_PARTS: Dict[str, Tuple[str, ...]] = {
    SSM_PROJ: (SSM_IN_PROJ, SSM_GATE_NORM, SSM_OUT_PROJ),
    DELTA_CONV: (DELTA_QK_NORM, DELTA_GATES),
    ROUTER: (ROUTER_CHOOSE, ROUTER_ORDER),
    FFN: (FFN_GATE, FFN_UP, FFN_DOWN),
}


def layer_scope(i: int) -> str:
    return f"{LAYER}{i}"


# ---- host: prefix of every span's TraceAnnotation
ANNOTATION_PREFIX = "rtfd:"
HOST_GC = "host.gc"          # one annotation per collection (tracing on)

# ---- host: the spans of one microbatch; (name, parent)
JOB_POLL = "job.poll"
JOB_DISPATCH = "job.dispatch_batch"
JOB_ADMIT = "job.admit"
ASSEMBLE = "assemble"
ASSEMBLE_ENCODE = "assemble.encode"
ASSEMBLE_FEATURES = "assemble.features"
ASSEMBLE_HISTORY = "assemble.history"
GRAPH = "graph"
ASSEMBLE_TOKENIZE = "assemble.tokenize"
PACK = "pack"
BUILD_PROGRAMS = "build_programs"    # a bucket's family compiled and run, on
                                     # its first split or routed batch only
DISPATCH = "dispatch"
JOB_COMPLETE = "job.complete_batch"
DEVICE_WAIT = "device_wait"
FINALIZE_RESPONSES = "finalize.responses"
FINALIZE_WRITE_BACK = "finalize.write_back"
JOB_FAN_OUT = "job.fan_out"
JOB_COMMIT = "job.commit"
BATCH_SPANS: Tuple[Tuple[str, str], ...] = (
    (JOB_POLL, ""),
    (JOB_DISPATCH, ""),
    (JOB_ADMIT, JOB_DISPATCH),
    (ASSEMBLE, JOB_DISPATCH),
    (ASSEMBLE_ENCODE, ASSEMBLE),
    (ASSEMBLE_FEATURES, ASSEMBLE),
    (ASSEMBLE_HISTORY, ASSEMBLE),
    (GRAPH, ASSEMBLE),
    (ASSEMBLE_TOKENIZE, ASSEMBLE),
    (PACK, JOB_DISPATCH),
    (BUILD_PROGRAMS, PACK),
    (DISPATCH, JOB_DISPATCH),
    (JOB_COMPLETE, ""),
    (DEVICE_WAIT, JOB_COMPLETE),
    (FINALIZE_RESPONSES, JOB_COMPLETE),
    (FINALIZE_WRITE_BACK, JOB_COMPLETE),
    (JOB_FAN_OUT, JOB_COMPLETE),
    (JOB_COMMIT, JOB_COMPLETE),
)
