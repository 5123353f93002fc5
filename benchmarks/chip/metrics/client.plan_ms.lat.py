"""Mean `serve.plan` span per batch: cluster pick, LWE encrypts, dispatch."""


def read(run):
    t = run.batch_timings()
    return 1e3 * sum(x.encode_s for x in t) / len(t) if t else None
