"""Query columns the answer kernel computes per real query: the engine's
`serve.answer.kernel_cols` over its `serve.encrypt.batched_queries`
(program counters).  1 when every batch's columns are computed as they
are; a kernel that pads each batch to a fixed tile reads tile / batch."""


def read(run):
    counts = run.obs.metrics_dict() if run.obs is not None else {}
    cols = counts.get("serve.answer.kernel_cols")
    queries = counts.get("serve.encrypt.batched_queries")
    return cols / queries if cols and queries else None
