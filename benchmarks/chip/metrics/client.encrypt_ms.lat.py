"""Mean `serve.plan.encrypt` span per batch: the client's public matrix A
and one LWE encrypt per query."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.plan.encrypt") if s else None
