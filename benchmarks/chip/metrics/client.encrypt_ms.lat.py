"""Mean `serve.plan.encrypt` span per batch: the dispatch of the batch's
one encrypt program (every query's LWE encrypt), under the server's
cached public matrix A."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.plan.encrypt") if s else None
