"""Process start → window start: corpus, build, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
