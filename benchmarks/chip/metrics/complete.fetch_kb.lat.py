"""KiB copied from the chips to the host per query: the engine's
`serve.complete.fetch_bytes` over its `serve.encrypt.batched_queries`
(program counters).  Each query fetches one decoded column, so this reads
the DB's height m / 1024."""


def read(run):
    counts = run.obs.metrics_dict() if run.obs is not None else {}
    fetched = counts.get("serve.complete.fetch_bytes")
    queries = counts.get("serve.encrypt.batched_queries")
    return fetched / queries / 1024 if fetched and queries else None
