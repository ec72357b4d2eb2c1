"""A percentile of one ``obs/tracing.Tracer`` stage over the completed
traces in the tracer's ring (``JobConfig.tracing``, traced run only).

In ``StreamJob`` the stage named ``ingest`` is broker produce stamp ->
admission, which is the wait in the broker and the microbatch assembler;
the stage named ``queue`` is only the admission loop itself."""

from benchmarks.harness import latency


def read(run, stage, q):
    if run.tracer is None:
        return None
    xs = [t.stages[stage] for t in run.tracer.traces(terminal="scored")
          if stage in t.stages]
    return latency.percentile(xs, q) if xs else None
