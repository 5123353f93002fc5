"""Share of the window in which device 0 ran nothing while the host was
inside `serve.plan`."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    if s is None or s.window_s <= 0:
        return None
    return 100.0 * s.idle_s(("serve.plan",)) / s.window_s
