"""What the scorer, the stream job and the fused program ask of a text encoder.

The text branch's configuration picks its encoder by its CLASS, and every
encoder is ONE row of ``TextEncoder``, written in the encoder's own file
(``models/<encoder>.TEXT_ENCODER``) and gathered by
``scoring/pipeline.TEXT_ENCODERS``. ``FraudScorer`` looks its row up once;
nothing in ``scoring/scorer.py``, ``scoring/pipeline.py`` or
``stream/job.py`` tests which kind of encoder it has. A further encoder is
its file with its row, one line of that table, its named scopes in
``obs/scopes.py`` and its tests (``docs/text_encoders.md``).

**A launch.** A microbatch reaches the device as one or two calls of the
fused program, each ``size`` bucket rows by ``width`` text positions, the
encoder's routed blocks (where it has any) compiled for ``capacity`` of
those ``size x width`` token slots. The row says which shapes a batch may
take: ``narrow_width`` (rows whose text fits it are launched apart from the
long ones, ``scoring/text_split.plan``) and ``capacities`` (the rungs a
launch's slots admit, ``scoring/text_split.capacities``: the host picks the
narrowest that holds the launch's real tokens). The first time a bucket is
launched at a shape either rule chose, the scorer builds every program the
rules can ask of that bucket under one ``build_programs`` span.

**The counters of a launch** (``LAUNCH_COUNTERS``: the keys
``StreamJob.counters`` starts with, beside its own, and sums batch by batch
from ``PendingScore.counters``; exact integers, no clock). Of every encoder,
counted at dispatch and summed over the batch's launches:

- ``token_slots``, ``token_slots_sq``: ``size x width`` and ``size x
  width^2`` (what attention's cost follows); ``real_tokens``: the mask's
  count over the real rows.
- ``short_text_rows``, ``long_text_rows``: real rows in a program narrower
  than ``text_len``, and at it (their sum is the batch's rows);
  ``split_batches``: 1 where the batch took two launches.
- ``expert_token_slots``: the capacity the routed blocks ran at (at most
  ``token_slots``; 0 where the encoder compacts nothing);
  ``compact_batches``: 1 where that was a narrow rung.

Of a causal encoder (every one reads its answer at a row's last real
token), from the rows' lengths L at dispatch: ``attn_visible_pairs_full`` =
sum of ``L(L+1)/2``, the (query, key) pairs the real queries see in one
causal layer, and ``attn_visible_pairs_sliding`` = sum of ``sum_i min(i+1,
window)`` where the class spells a ``sliding_window`` (else 0).

Where a counter is "x layers" it is times the layers OF THAT KIND: an
encoder whose layers are one mixer each (``models/nemotron_h.py``) counts
its routed layers in ``routed_pairs`` and ``expert_*``, its state-space
layers in ``ssm_chunks``, and ``attn_visible_pairs_full`` is one causal
layer's whatever the number of attention layers.

Of an encoder with routed blocks: ``routed_pairs`` at dispatch, the (token,
expert) pairs its routers chose (real tokens x experts a token x sparse
layers: padding is not routed); from the launch plan alone,
``dispatch_rows``, the pair rows its routed layers gather into expert order
(the slots the routed blocks ran at x experts a token x sparse layers), and
``dispatch_kernel_rows``, those of them that a launch asked for its kernels
at a shape ``ops.dispatch.dispatch_supported`` takes sent through the row
fetch (0 under XLA's gather); and at finalize, from the program's second
output ``i32[3, sparse layers]`` (``models/olmoe.launch_stats``: each
layer's largest expert group, the pairs that entered a held expert's group,
the rows the fused gate / up kernel's grid visited): ``expert_rows`` = the
held pairs (all of ``routed_pairs`` where a layer holds every expert),
``expert_peak_rows`` = sum over layers of largest group x ``num_experts``
(what the launch would cost were every group as large as the largest),
``expert_tile_rows`` = the visited rows (0 in the XLA form). Its class
spells, under the Hugging Face names, ``num_experts`` (the experts a layer
HOLDS), ``num_experts_per_tok``, ``hidden_size``, ONE expert's width
(``moe_intermediate_size`` where the source spells one, else
``intermediate_size``: ``expert_width``) and ``num_sparse_layers``.

Of an encoder with a state-space mixer: ``ssm_chunks`` at dispatch, the
chunks its scans walked (``token_slots`` / the scan's chunk x the layers
that have such a mixer); of one with a delta-rule mixer
(``models/qwen3_next.py``) ``delta_chunks``, the same of its scans.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, FrozenSet, Optional, Sequence, Tuple

import numpy as np

from realtime_fraud_detection_tpu.ops.combine import combine_supported
from realtime_fraud_detection_tpu.ops.dispatch import (
    dispatch_supported,
    dispatch_takes,
)
from realtime_fraud_detection_tpu.ops.grouped_matmul import (
    gmm_tiling,
    grouped_matmul_supported,
)

LAUNCH_COUNTERS: Tuple[str, ...] = (
    "token_slots", "token_slots_sq", "real_tokens",
    "expert_rows", "expert_peak_rows", "expert_tile_rows",
    "expert_token_slots", "compact_batches",
    "dispatch_rows", "dispatch_kernel_rows",
    "routed_pairs", "attn_visible_pairs_full", "attn_visible_pairs_sliding",
    "ssm_chunks",
    "short_text_rows", "long_text_rows", "split_batches",
    "delta_chunks",
)

# the planes a deployment may ask the text branch to run under; a row names
# those its encoder takes, and ``FraudScorer.plane_refusal`` says the rest
INT8, DEQUANT, MESH, POOL, TEXT_SPLIT = (
    "int8", "dequant_matmul", "mesh", "pool", "text_split")
EVERY_PLANE = frozenset((INT8, DEQUANT, MESH, POOL, TEXT_SPLIT))


@dataclasses.dataclass(frozen=True)
class KernelSite:
    """One place of an encoder's program that holds a Pallas kernel or its
    XLA form; ``FraudScorer.kernel_snapshot()`` counts every launch at each.
    ``refusal(config, width, slots)`` is why a launch of ``width`` positions,
    its routed blocks at ``slots`` token slots, keeps the XLA form here even
    where the program is asked for its kernels, or None where it holds the
    kernel: the predicate the traced guard consults. ``by_width`` says the
    reason follows from the width alone, so that the snapshot can name it
    with no launch in hand (``kernel_snapshot()["refused"]``)."""

    name: str
    refusal: Callable[[Any, int, int], Optional[str]]
    by_width: bool = True


def _nothing(*_: Any) -> None:
    return None


def _empty(*_: Any) -> Dict[str, Any]:
    return {}


@dataclasses.dataclass(frozen=True)
class TextEncoder:
    """One encoder's row (the module's docstring is the contract).

    ``init(key, config)`` draws the parameters; ``predict(params, ids,
    mask, config, *, use_pallas, kernel_interpret, capacity,
    dequant_kernel)`` returns ``(probability f32[B], statistics or None)``
    — the program has a second output exactly where the statistics are an
    array; ``depth(config)`` is the number of layers, however the source
    spells it. ``sites`` are the kernel sites a launch is counted at,
    ``planes`` the planes the encoder runs under (``one_device`` words the
    refusal of a mesh of several). ``narrow_width(config)`` and
    ``capacities(slots)`` are the launch rule (None: no such shape);
    ``build_ids(config, programs)`` adds ids to a bucket's
    ``build_programs`` span from its ``(rows, width, capacity)`` members.
    ``dispatch_counters(config, launches, lengths)`` and
    ``finalize_counters(config, stats)`` return the encoder's own counters
    of a batch (``launches`` have ``size``, ``width``, ``capacity`` and
    ``kernels``, whether the program was asked for its kernels;
    ``lengths`` are the real rows' token counts; ``stats`` is the program's
    second output on the host)."""

    config_class: type
    init: Callable[..., Dict[str, Any]]
    predict: Callable[..., Tuple[Any, Optional[Any]]]
    depth: Callable[[Any], int]
    sites: Tuple[KernelSite, ...]
    planes: FrozenSet[str] = frozenset()
    one_device: str = ""
    narrow_width: Callable[[Any], Optional[int]] = _nothing
    capacities: Callable[[int], Optional[Tuple[int, ...]]] = _nothing
    build_ids: Callable[[Any, Sequence[tuple]], Dict[str, str]] = _empty
    dispatch_counters: Callable[..., Dict[str, int]] = _empty
    finalize_counters: Callable[..., Dict[str, int]] = _empty


def launch_counters(encoder: TextEncoder, config: Any,
                    launches: Sequence[Any], lengths: np.ndarray,
                    full: int) -> Dict[str, int]:
    """The counters of one batch at dispatch: what every encoder's launches
    count, then the encoder's own. ``full`` is the width the batch was
    tokenised to."""
    n = len(lengths)
    token_slots = sum(la.size * la.width for la in launches)
    short_rows = n - sum(la.n for la in launches if la.width == full)
    expert_slots = launches[0].capacity or 0
    counters = dict.fromkeys(LAUNCH_COUNTERS, 0)
    counters.update(
        token_slots=token_slots,
        token_slots_sq=sum(la.size * la.width * la.width for la in launches),
        real_tokens=int(lengths.sum()),
        short_text_rows=short_rows, long_text_rows=n - short_rows,
        split_batches=int(len(launches) > 1),
        expert_token_slots=expert_slots,
        compact_batches=int(0 < expert_slots < token_slots))
    counters.update(encoder.dispatch_counters(config, launches, lengths))
    return counters


# ------------------------------------------------ what causal encoders share
def visible_pairs(config: Any, lengths: np.ndarray) -> Tuple[int, int]:
    """The (query, key) pairs the real queries of rows of ``lengths`` real
    tokens see in one causal layer, ``L(L+1)/2`` a row, and in one layer
    under the encoder's ``sliding_window`` W (0 where its class spells
    none): ``sum_i min(i+1, W)`` = the same less the ``(L-W)(L-W+1)/2``
    pairs further back than the window."""
    lengths = lengths.astype(np.int64)
    full = int(np.sum(lengths * (lengths + 1) // 2))
    window = getattr(config, "sliding_window", None)
    if not window:
        return full, 0
    beyond = np.maximum(lengths - window, 0)
    return full, full - int(np.sum(beyond * (beyond + 1) // 2))


def causal_counters(config: Any, launches: Sequence[Any],
                    lengths: np.ndarray) -> Dict[str, int]:
    full, sliding = visible_pairs(config, lengths)
    return {"attn_visible_pairs_full": full,
            "attn_visible_pairs_sliding": sliding}


# ------------------------------------------- what the routed encoders share
def expert_width(config: Any) -> int:
    """ONE routed expert's width: the source's ``moe_intermediate_size``
    where it spells one (its ``intermediate_size`` may then be a dense
    layer's: ``models/qwen3_next.py``), else ``intermediate_size``."""
    return getattr(config, "moe_intermediate_size", None) \
        or config.intermediate_size


def _gate_up_refusal(config: Any, width: int, slots: int) -> Optional[str]:
    rows = slots * config.num_experts_per_tok
    return None if grouped_matmul_supported(
        rows, config.hidden_size, expert_width(config)) else (
        f"the grouped expert matmul takes whole lane tiles: {rows} rows")


def _combine_refusal(config: Any, width: int, slots: int) -> Optional[str]:
    return None if combine_supported(
        slots, config.num_experts_per_tok, config.hidden_size) else (
        f"the experts' combine takes whole blocks of tokens: {slots}")


def _dispatch_shape(config: Any, slots: int) -> Tuple[int, int, int, int]:
    """What ``dispatch_supported`` is asked of a launch's routed blocks, at
    the checkpoint's bfloat16 (float32 weights are the tests')."""
    return (slots, slots * config.num_experts_per_tok, config.hidden_size, 2)


def _dispatch_refusal(config: Any, width: int, slots: int) -> Optional[str]:
    n, pairs, hidden, itemsize = _dispatch_shape(config, slots)
    if dispatch_supported(n, pairs, hidden, itemsize):
        return None
    if not dispatch_takes(pairs, hidden, itemsize):
        return ("the experts' row fetch takes rows of whole 32-bit lane "
                f"tiles in whole blocks: {pairs} rows of {hidden}")
    return ("XLA's gather reads a source of "
            f"{n * hidden * itemsize >> 20} MiB about once: {slots} slots")


def _routed_capacities(slots: int) -> Tuple[int, ...]:
    # scoring/ imports models/: the rule is reached when it is asked
    from realtime_fraud_detection_tpu.scoring import text_split

    return text_split.capacities(slots)


def _routed_dispatch_counters(config: Any, launches: Sequence[Any],
                              lengths: np.ndarray) -> Dict[str, int]:
    a_slot = config.num_experts_per_tok * config.num_sparse_layers
    slots = [(la, la.capacity or la.size * la.width) for la in launches]
    return dict(causal_counters(config, launches, lengths),
                routed_pairs=int(lengths.sum()) * a_slot,
                dispatch_rows=sum(n for _, n in slots) * a_slot,
                dispatch_kernel_rows=sum(
                    n for la, n in slots if la.kernels
                    and dispatch_supported(*_dispatch_shape(config, n))
                ) * a_slot)


def _routed_finalize_counters(config: Any, stats: np.ndarray
                              ) -> Dict[str, int]:
    peaks, held, tile_rows = (
        int(total) for total in np.sum(stats, axis=1, dtype=np.int64))
    return {"expert_rows": held,
            "expert_peak_rows": peaks * config.num_experts,
            "expert_tile_rows": tile_rows}


def expert_tiles(config: Any, programs: Sequence[tuple], matrices: int = 2
                 ) -> Dict[str, str]:
    """The id ``tiles`` of a routed bucket's ``build_programs`` span: for
    each of its programs' capacities, the (tm, tk, tn) its two grouped
    calls run at, ``<slots>:<gate / up's>+<down's>`` joined by commas, so
    that a trace and every compile-ledger record the span caused say which
    tiles the programs hold. The host's mirror of what the traced code asks
    (``ops.grouped_matmul.gmm_tiling``, by the same shapes; ``matrices``:
    the right-hand blocks a step of the experts' first call); nothing where
    a capacity runs the XLA form."""
    hidden, width = config.hidden_size, expert_width(config)
    tiles = []
    for _, _, rung in programs:
        rows = rung * config.num_experts_per_tok
        if grouped_matmul_supported(rows, hidden, width):
            tiles.append(f"{rung}:" + "+".join(
                "x".join(map(str, gmm_tiling(rows, k, n, config.num_experts,
                                             gated=gated, matrices=matrices)))
                for gated, k, n in ((True, hidden, width),
                                    (False, width, hidden))))
    return {"tiles": ",".join(tiles)} if tiles else {}


def routed_encoder(config_class: type, init: Callable[..., Dict[str, Any]],
                   predict: Callable[..., Any],
                   attention_refusal: Callable[[Any, int], Optional[str]],
                   *, sites: Tuple[KernelSite, ...] = (),
                   dispatch_counters: Callable[..., Dict[str, int]] = _empty,
                   expert_matrices: int = 2) -> TextEncoder:
    """The row of a causal encoder with routed sparse-expert blocks over
    ``models/olmoe.py``'s machinery: ``predict`` takes ``capacity`` and,
    with ``with_stats``, returns the launch's statistics beside the
    probability; ``attention_refusal(config, width)`` is its attention
    site's predicate (a fused causal core, or ZAYA1's fused mixing).
    ``sites`` and ``dispatch_counters`` are what the encoder has BESIDE its
    attention and its routed blocks (a state-space mixer's scan and the
    chunks it walked): further kernel sites, and counters of a batch at
    dispatch added to the routed ones. ``expert_matrices``: what an
    expert's first grouped call streams a step — gate and up, or 1 where
    the experts have no gate."""

    def routed_predict(params, ids, mask, config, *, use_pallas,
                       kernel_interpret, capacity, dequant_kernel):
        return predict(params, ids, mask, config, capacity=capacity,
                       use_pallas=use_pallas,
                       kernel_interpret=kernel_interpret, with_stats=True)

    return TextEncoder(
        config_class=config_class, init=init, predict=routed_predict,
        depth=lambda config: config.num_hidden_layers,
        sites=(KernelSite("attention",
                          lambda c, width, slots: attention_refusal(c, width)),
               KernelSite("expert_gate_up", _gate_up_refusal, by_width=False),
               KernelSite("expert_dispatch", _dispatch_refusal,
                          by_width=False),
               KernelSite("expert_combine", _combine_refusal,
                          by_width=False)) + sites,
        planes=frozenset((TEXT_SPLIT,)),
        one_device="the grouped expert matmul; a routed",
        capacities=_routed_capacities,
        build_ids=lambda config, programs: expert_tiles(
            config, programs, expert_matrices),
        dispatch_counters=lambda config, launches, lengths: dict(
            _routed_dispatch_counters(config, launches, lengths),
            **dispatch_counters(config, launches, lengths)),
        finalize_counters=_routed_finalize_counters)
