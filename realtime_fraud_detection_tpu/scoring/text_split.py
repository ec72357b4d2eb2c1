"""Which text widths a microbatch is launched at.

One static ``ScorerConfig.text_len`` makes every row pay for the longest
text the deployment admits. Where the text kernel also takes a narrower
width (``ops.attention.narrowest_supported_len``), the rows whose real
tokens all lie inside it can run in a program compiled at that width, and
only the rest at ``text_len``. The rule below maps (short rows, long rows)
of a bucket-``size`` batch to its launches, each ``(rows, width)`` one
compiled program, and ``family`` lists every program the rule can ask of a
bucket: what the scorer compiles the first time that bucket leaves the
unsplit launch, so that none first appears under load.

The rule is decided from shapes alone. A batch is split only where its
launches together hold fewer (row, position) slots than the one launch at
``text_len`` — fewer slots is less work in every kernel of the text branch,
all of them linear or quadratic in the padded length.

A sparse encoder's routed block (``models/olmoe.routed_block``) has a second such
shape, its **capacity**: how many of a launch's slots the router, the row
gatherings and the grouped expert matmuls are compiled for. The real tokens
of the launch are compacted into it, so it has to hold them all; who holds
the mask picks it: the host, per batch, from the count it already makes
(``capacity``), out of the few rungs a launch's slots admit (``capacities``:
what the scorer compiles the first time a bucket is launched). A rung is a
shape of the program, never a limit on the tokens: a batch no narrow rung
holds runs at every slot.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

# which rows of the batch a launch holds
ALL, SHORT, LONG = "all", "short", "long"

# a batch is split while its long rows fit this share of its bucket: past
# it the two launches hold over three eighths of the unsplit one's slots
# and the second program's fixed cost is no longer worth it
LONG_BUCKET_SHARE = 8
# the smallest row bucket a long part is launched on: a long part of one
# row on bucket 1 would be a program almost no batch needs (and each costs
# seconds of set-up), while 8 rows at ``text_len`` cost little more than 1
MIN_LONG_ROWS = 8

# the narrow rung of a routed block: this share of the launch's slots. The
# deployed mix fills 65% of its slots (+-1.3 a batch of 256 rows), so three
# quarters holds every batch of it with room; a rung nearer the mix would
# send a batch to the full program for one long row too many
CAPACITY_SHARE = (3, 4)
# capacities are whole multiples of this many tokens: times OLMoE's 8
# experts a token that is whole 128-row tiles
# (ops.grouped_matmul_supported). With one expert a token (ZAYA1) the rung
# of every bucket that gets one at 128 positions (32 rows and up: 96 rows x
# 32) is whole tiles too; a rung that were not would run ragged_dot
CAPACITY_MULTIPLE = 16
# the smallest launch that gets a narrow rung. Under it an expert's group is
# tens of rows, its matmuls are paced by reading the expert's weights
# whatever the rows, and a second program (seconds of set-up each) buys
# nothing
MIN_COMPACT_SLOTS = 4096

# (which rows, bucket rows, text width)
Launch = Tuple[str, int, int]


def plan(n_short: int, n_long: int, size: int, narrow: Optional[int],
         full: int, bucket_of: Callable[[int], int]) -> Tuple[Launch, ...]:
    """The launches of a batch of ``n_short + n_long`` rows padded to
    ``size``: ``narrow`` is the narrower width (None where there is none),
    ``full`` the configured ``text_len``, ``bucket_of`` the scorer's row
    bucketing. A short part stays on the batch's own bucket, so a bucket's
    family does not depend on how many rows were short (and the scorer can
    leave the long rows in it, cut short, where it would pad: their answers
    come from the long launch)."""
    unsplit = ((ALL, size, full),)
    if narrow is None or narrow >= full or n_short == 0:
        return unsplit
    if n_long == 0:
        return ((ALL, size, narrow),)
    if n_long * LONG_BUCKET_SHARE > size:
        return unsplit
    long_rows = bucket_of(max(n_long, MIN_LONG_ROWS))
    if size * narrow + long_rows * full >= size * full:
        return unsplit
    return ((SHORT, size, narrow), (LONG, long_rows, full))


def family(size: int, narrow: Optional[int], full: int,
           bucket_of: Callable[[int], int]) -> Tuple[Tuple[int, int], ...]:
    """Every ``(rows, width)`` program ``plan`` can ask of bucket ``size``,
    the unsplit one first."""
    programs = [(size, full)]
    # the rule reads the long rows and whether any row is short: full
    # batches with every count of long rows reach all it can answer
    for n_long in range(size):
        for _, rows, width in plan(size - n_long, n_long, size, narrow, full,
                                   bucket_of):
            if (rows, width) not in programs:
                programs.append((rows, width))
    return tuple(programs)


def capacities(slots: int) -> Tuple[int, ...]:
    """The capacities a routed block is compiled at for a launch of
    ``slots`` (rows x width) slots, narrowest first; the last is every
    slot."""
    if slots < MIN_COMPACT_SLOTS:
        return (slots,)
    num, den = CAPACITY_SHARE
    narrow = slots * num // den // CAPACITY_MULTIPLE * CAPACITY_MULTIPLE
    return (narrow, slots)


def capacity(real_tokens: int, slots: int) -> int:
    """The narrowest rung of ``capacities(slots)`` that holds a launch's
    ``real_tokens``."""
    for rung in capacities(slots):
        if real_tokens <= rung:
            return rung
    raise ValueError(
        f"text_split.capacity: {real_tokens} real tokens in a launch of "
        f"{slots} slots")
