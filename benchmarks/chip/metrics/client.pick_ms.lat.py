"""Mean `serve.plan.pick` span per batch: the cluster pick's distances,
dispatched and fetched to the host, with the wait for the device work
queued ahead of them on the chip's one stream."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.plan.pick") if s else None
