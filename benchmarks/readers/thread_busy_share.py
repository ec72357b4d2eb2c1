"""Percent of the counted window the job's one thread spent on anything but
waiting: 100 x (window - the ``total_s`` of the ``waiting`` spans) / window,
the window being window-open to the read of the spans. The spans are the
program's (``SpanTimer``): the wait for the device and the poll for rows."""


def read(run, waiting):
    window = run.t_count_snap - run.t_open
    if window <= 0 or any(s not in run.stages for s in waiting):
        return None
    idle = sum(run.stages[s]["total_s"] for s in waiting)
    return 100.0 * (window - idle) / window
