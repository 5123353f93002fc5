"""Offline k-means and packing, as `PirRagSystem.index_seconds` times it."""


def read(run):
    return run.build["index_s"]
