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
