"""serve_mfu: model FLOPs of each window request's prefill and of the
decode steps whose tokens are used, over the window (host clock) and
the chip's bf16 peak, in percent."""


def read(run):
    v = run.record.values
    stamps = [s for s in v["token_stamps"] if s]
    if not stamps:
        return None
    ref, conf = run.cell.reference, run.cell.config
    B, P, G = v["batch"], v["prompt_len"], v["gen_len"]
    per_request = ref.prefill_flops(conf, B, P) + sum(
        ref.decode_flops(conf, B, P + j) for j in range(1, G))
    secs = stamps[-1][-1] - v["window_start"]
    return (100.0 * per_request * len(stamps) / secs
            / (run.peaks["bf16_flops_per_s"] * run.cell.chips))
