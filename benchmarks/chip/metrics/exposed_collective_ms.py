"""exposed_collective_ms: per step, the collective time on a chip
during which no other operation runs on it (the λ-decode psums and the
sharded state's gathers), averaged over the chips, from the trace."""
from chipbench import trace as tr


def read(run):
    t = run.trace
    if t is None:
        return None
    lo, hi = t.window
    steps = [s for s in t.host.get("step", []) if lo <= s[1] <= hi]
    devs = sorted(t.devices)
    if not steps or not any(tr.collective_intervals(t.ops(d))
                            for d in devs):
        return None
    exposed = sum(tr.exposed_collective_s(t, d) for d in devs) / len(devs)
    return 1e3 * exposed / len(steps)
