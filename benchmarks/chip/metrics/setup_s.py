"""setup_s: process start to window start (loading, weights, compiles,
warm-up), host clock."""


def read(run):
    return run.record.values["setup_s"]
