"""The open-loop load generator, in a process of its own.

In the job's process the producer shared the interpreter lock with the job:
it ran 5-8 ms late at the 99th percentile, over a tenth of the median
latency it was there to measure (my chip run, PR 22). Here it has an
interpreter to itself. It stays off the chip (``JAX_PLATFORMS=cpu``; it
builds events and sends them, nothing else), makes the SAME stream as the
parent from the same seed, and submits each event when due through
``IngressGateway`` -> ``NetBrokerClient`` -> the ``BrokerServer`` thread in
the job's process, whose ``InMemoryBroker`` the job reads directly.

Protocol: prints ``ready`` when built and connected; reads the stream's
start time (``time.time()`` seconds) from stdin; submits; closes the
gateway; saves its per-event submit stamps and the gateway's drop count to
``--out`` (``numpy.savez``).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"      # never the chip: the job holds it

import argparse                  # noqa: E402
import sys                       # noqa: E402
from pathlib import Path         # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import numpy as np               # noqa: E402

from benchmarks.harness import events as ev_mod, load, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--topic", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from realtime_fraud_detection_tpu.stream import IngressGateway
    from realtime_fraud_detection_tpu.stream.netbroker import NetBrokerClient

    cell = spec.cell(args.workload)
    made = ev_mod.make_stream(cell, args.seed, args.seconds)
    stream = made.pool.materialize(range(len(made.offsets)), made.offsets)
    client = NetBrokerClient("127.0.0.1", args.port)
    gateway = IngressGateway(client, args.topic)
    print(f"ready native={int(gateway.native)}", flush=True)
    t_base = float(sys.stdin.readline())
    producer = load.OpenLoopProducer(gateway.submit, stream,
                                     t_base + made.offsets)
    try:
        # the pre-built stream is millions of live objects: a full
        # collection here stalled the schedule ~120 ms (my chip run, PR 22)
        with load.collector_off():
            producer.run()          # this thread: nothing else to do here
    finally:
        gateway.close()
        client.close()
    np.savez(args.out, submitted=producer.submitted,
             dropped=gateway.dropped)
    return 0


if __name__ == "__main__":
    sys.exit(main())
