"""Mean `serve.complete` span per batch: fetch, parse and rerank."""


def read(run):
    t = run.batch_timings()
    return 1e3 * sum(x.decode_s for x in t) / len(t) if t else None
