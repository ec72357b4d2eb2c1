"""Program utilisation, not a kernel's roofline share: the matmul FLOPs
the full-bucket program needs (``harness/flops.py``) times the batches
completed in the traced slice, over device-busy time, over the chip's
published bf16 peak. Only where every batch fills the bucket."""

from benchmarks.harness import peaks


def read(run):
    batches = run.counters_slice.get("batches", 0)
    rows = run.counters_slice.get("scored", 0)
    if run.trace is None or not batches:
        return None
    if rows < 0.99 * batches * run.extra["cfg"]["job"]["max_batch"]:
        return None
    busy = sum(run.trace["per_device"].values())
    peak = peaks.peak(run.extra["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * run.extra["flops_per_batch"] * batches / busy / peak
