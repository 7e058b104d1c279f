"""serve_tokens_per_s: generated tokens that reached the host in the
window over the window, which ends when the last request's last token
reached the host (host clock)."""


def read(run):
    v = run.record.values
    stamps = [s for s in v["token_stamps"] if s]
    if not stamps:
        return None
    tokens = sum(len(s) for s in stamps) * v["batch"]
    return tokens / (stamps[-1][-1] - v["window_start"])
