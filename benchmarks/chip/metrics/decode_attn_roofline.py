"""decode_attn_roofline: the fused decode-attention kernel's share of
its roofline.  Bytes per call: q and out (batch x heads x head_dim, in
the compute dtype) and the whole K and V ring caches (batch x cache
slots x kv heads x head_dim, in the cache's dtype), read once; the
least time is those bytes at the chip's HBM bandwidth (the kernel is
memory-bound: 4 FLOP per cache element against 2 x 4 bytes).  Kernel
time is the device time of its trace events per call.

The kernel has no stable name: its events are matched by the name the
trace gives them today, that of the jitted wrapper around the Pallas
call (``%decode_attention_fwd.N``).  A rename silences the metric.
"""
from chipbench import trace as tr

KERNEL = r"^%?decode_attention_fwd"


def read(run):
    if run.trace is None:
        return None
    secs, calls = tr.op_seconds(run.trace, KERNEL)
    if calls == 0:
        return None
    v, m = run.record.values, run.cell.config["model"]
    B, H, Kv, Dh = v["batch"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    nbytes = (2 * B * H * Dh * v["q_itemsize"]
              + 2 * B * v["cache_len"] * Kv * Dh * v["cache_itemsize"])
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / (secs / calls)
