"""Answer kernel's share of its roofline, from the device trace.

Least time of each answer is max(bytes / HBM bandwidth, int8 ops / MXU
peak), with bytes and ops counted from shapes over the batch's real query
columns (``work.py``) and the peaks from ``peaks.json``; the kernel time is
the device duration of its ``jit_modmatmul_pallas`` programs.  Kernel
events pair with the batches dispatched in the window, in order.
"""
import work

KERNEL = "jit_modmatmul_pallas"


def read(run):
    if run.trace is None or not run.peaks:
        return None
    spent = [d for name, _, d in run.trace.modules if name == KERNEL]
    n = min(len(spent), len(run.batches))
    if n == 0:
        return None
    least = sum(work.least_seconds(run.m, run.n, b.b,
                                   run.peaks["int8_ops_per_s"],
                                   run.peaks["hbm_bytes_per_s"])[0]
                for b in run.batches[:n])
    return 100.0 * least / sum(spent[:n])
