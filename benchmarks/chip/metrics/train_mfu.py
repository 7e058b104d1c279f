"""train_mfu: model FLOPs of forward and backward over every coded row
the code assigns (the configuration's own count, no recomputation),
times the steps of the traced window, over the window (host clock) and
the chips' bf16 peak, in percent."""


def read(run):
    v = run.record.values
    steps = [s for s in run.record.spans["step"]
             if s[0] >= v["window_start"]]
    if not steps:
        return None
    per_step = (run.cell.reference.train_flops_per_token(
        run.cell.config, v["seq_len"]) * v["rows_per_step"] * v["seq_len"])
    secs = steps[-1][1] - v["window_start"]
    peak = run.peaks["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * per_step * len(steps) / secs / peak
