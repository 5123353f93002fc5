"""Answer kernel's share of its roofline, from the device trace.

Least time of each answer is max(bytes / HBM bandwidth, int8 ops / MXU
peak), with bytes and ops counted from shapes over the batch's real query
columns and the rows one chip holds (``work.py``; all ``m`` on one chip,
the DB's shard height on a row-sharded one), against one chip's peaks from
``peaks.json``.  The kernel time is the device duration, on device 0, of
the answer programs: ``jit_modmatmul_pallas`` on one chip, ``jit_local``
(the ``shard_map`` of ``collectives.row_shard_gemm``) on a row-sharded DB.
Kernel events pair with the batches dispatched in the window, in order.
"""
import work


def read(run):
    if run.trace is None or not run.peaks:
        return None
    spent = [d for name, _, d in run.trace.modules
             if name in work.ANSWER_MODULES]
    n = min(len(spent), len(run.batches))
    if n == 0:
        return None
    least = sum(work.least_seconds(run.shard_rows, run.n, b.b,
                                   run.peaks["int8_ops_per_s"],
                                   run.peaks["hbm_bytes_per_s"])[0]
                for b in run.batches[:n])
    return 100.0 * least / sum(spent[:n])
