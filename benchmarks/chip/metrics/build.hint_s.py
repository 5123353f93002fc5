"""Offline hint GEMM H = D·A, as `PirRagSystem.hint_seconds` times it."""


def read(run):
    return run.build["hint_s"]
