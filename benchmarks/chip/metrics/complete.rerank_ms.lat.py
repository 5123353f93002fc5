"""Mean `serve.complete.rerank` span per batch: every request's top k by
cosine over its parsed cluster, on the host."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.complete.rerank") if s else None
