"""Mean `serve.complete.parse` span per batch: every decoded cluster of the
batch parsed back into passages on the host."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.complete.parse") if s else None
