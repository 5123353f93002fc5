"""Device time per batch of the programs after the answer kernel.

From the end of each answer program on device 0 (``jit_modmatmul_pallas``
on one chip, ``jit_local``, the ``shard_map`` of
``collectives.row_shard_gemm``, on a row-sharded DB) to the next tick's
first program (its key split, ``jit__threefry_split``): the cut of the
answer to the batch's columns, the modulus switch, and the client's hint
strip and decode that produce the cluster bytes.
"""
import work

NEXT = "jit__threefry_split"


def read(run):
    if run.trace is None:
        return None
    per_batch, cur = [], None
    for name, _, dur in run.trace.modules:
        if name in work.ANSWER_MODULES:
            cur = 0.0
        elif cur is not None and name == NEXT:
            per_batch.append(cur)
            cur = None
        elif cur is not None:
            cur += dur
    return 1e3 * sum(per_batch) / len(per_batch) if per_batch else None
