"""Process start -> window open, in seconds (loading, warming up and, in
a run that compiles, compilation)."""


def read(run):
    return run.extra["setup_s"]
