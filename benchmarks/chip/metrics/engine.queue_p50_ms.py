"""Median wait from due time to the start of the batch plan (engine span)."""


def read(run):
    waits = [(r.timing.t_plan - run.due[rid]) * 1e3
             for rid, r in run.responses.items()
             if not r.failed and r.timing is not None]
    return run.pct(waits, 50) if waits else None
