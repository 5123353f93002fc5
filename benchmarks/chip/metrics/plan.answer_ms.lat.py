"""Mean `serve.plan.dispatch.answer` span per batch: the encrypted queries
placed on the DB's chips and the answer program enqueued
(`PIRServer.answer`)."""
import serve_spans


def read(run):
    s = serve_spans.of_run(run)
    return s.mean_ms("serve.plan.dispatch.answer") if s else None
