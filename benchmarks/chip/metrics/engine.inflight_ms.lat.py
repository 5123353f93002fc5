"""How long a dispatched batch waits to be retired: mean, per batch joined
by `bid`, of its `serve.gemm` start − its `serve.plan` end."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.inflight_ms() if s else None
