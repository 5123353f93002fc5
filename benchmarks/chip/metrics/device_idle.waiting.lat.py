"""Share of the window in which device 0 ran nothing while the host was
inside none of `serve.plan`, `serve.gemm` and `serve.complete`: the engine
waiting for arrivals or the batcher's deadline."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    if s is None or s.window_s <= 0:
        return None
    busy = s.idle_s(serve_spans.ENGINE_STAGES)
    return 100.0 * (s.idle_s() - busy) / s.window_s
