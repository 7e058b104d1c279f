"""train_tokens_per_s: useful (decoded) tokens of every step the window
ran, K x part_batch x seq_len each, over the time from window start to
the end of its last step (host clock).  Redundant coded rows do not
count."""


def read(run):
    steps = [s for s in run.record.spans["step"]
             if s[0] >= run.record.values["window_start"]]
    if not steps:
        return None
    t0 = run.record.values["window_start"]
    return (len(steps) * run.record.values["useful_tokens_per_step"]
            / (steps[-1][1] - t0))
