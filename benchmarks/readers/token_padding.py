"""Percent of the text branch's launched (row, position) slots that hold no
real token, over the counted part: 100 x (1 - ``real_tokens`` /
``token_slots``), both ``StreamJob.counters`` (exact integers)."""


def read(run):
    slots = run.counters.get("token_slots", 0)
    if not slots:
        return None
    return 100.0 * (1.0 - run.counters["real_tokens"] / slots)
