"""A kernel's share of its roofline, in percent: what the algorithm needs
for the launches of the traced slice (``kernels/<kernel>.py``, from the
program's own counters) over one published peak of the chip
(``harness/peaks.py``), over the device time under the kernel's scope in
the slice. ``peak`` says which roofline bounds the kernel at the deployed
shapes — the kernel's file says why: bytes over ``hbm_bytes_per_s``,
operations over any other peak."""

from benchmarks.harness import peaks, scopes, spec


def read(run, scope, kernel, peak="bf16_flops_per_s"):
    seconds = scopes.scope_seconds(run, scope)
    if not seconds:
        return None
    quantity = "hbm_bytes" if peak == "hbm_bytes_per_s" else "flops"
    needed = spec.kernel(kernel).work(
        run.counters_slice, run.extra["cfg"])[quantity]
    if not needed:
        print(f"[bench] scopes: the program counted no tokens in the slice "
              f"(counters {sorted(run.counters_slice)}); {kernel} roofline "
              f"left out", flush=True)
        return None
    return 100.0 * needed / peaks.peak(run.extra["device"]["kind"], peak) \
        / seconds
