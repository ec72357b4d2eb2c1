"""A kernel's share of its roofline, in percent: the FLOPs the program
issued for it over the traced slice (``harness/kernel_flops.py``, from the
program's own token counters) over the chip's published bf16 peak, over
the device time under the kernel's scope in the slice. Both kernels are
compute-bound at the deployed shapes (see ``kernel_flops``)."""

from benchmarks.harness import kernel_flops, peaks, scopes


def read(run, scope, kernel):
    seconds = scopes.scope_seconds(run, scope)
    if not seconds:
        return None
    flops = kernel_flops.issued(kernel, run.counters_slice, run.extra["cfg"])
    if not flops:
        print(f"[bench] scopes: the program counted no tokens in the slice "
              f"(counters {sorted(run.counters_slice)}); {kernel} roofline "
              f"left out", flush=True)
        return None
    peak = peaks.peak(run.extra["device"]["kind"], "bf16_flops_per_s")
    return 100.0 * flops / peak / seconds
