"""Mean `serve.plan.dispatch.decode` span per batch: the batched hint strip
and decode enqueued behind the answer (`PIRClient.recover_batch`)."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.plan.dispatch.decode") if s else None
