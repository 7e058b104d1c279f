"""batch_build_ms: mean host time per window step in the session's
``build_batch`` (the coded batch: per-part rows, coefficients)."""


def read(run):
    t0 = run.record.values["window_start"]
    spans = [b - a for a, b in run.record.spans["build_batch"] if a >= t0]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
