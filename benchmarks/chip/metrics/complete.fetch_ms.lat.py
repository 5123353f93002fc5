"""Mean `serve.complete.fetch` span per batch: the decoded cluster bytes
copied from the device to the host."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.complete.fetch") if s else None
