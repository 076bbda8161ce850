"""Paged decode attention's share of its roofline, in percent: the least
time for the decode steps in the traced window (`costs/
paged_decode_attn.py`: live context only, K/V at 0.9375 B/value), over
the device time of the `sparq_paged_decode_attn` kernel there."""
from bench.costs import paged_decode_attn as cost
from bench.observe import least_time, per_execution


def read(obs):
    tr, p = obs.trace, obs.peaks
    secs = tr.kernel_ns.get(("step", "sparq_paged_decode_attn"), 0.0) * 1e-9
    if secs <= 0:
        return None
    L = obs.sizes["layers"]
    need = []
    for s in obs.steps_in():
        ops, nbytes = cost.step(obs.sizes, s.ctx)
        # one kernel call per layer, each its own share of the step
        need.append(L * least_time(ops / L, nbytes / L, p[cost.PEAK],
                                   p["hbm_bytes_per_s"]))
    total = per_execution(need, tr.module_count("step"))
    return 100.0 * total / secs if total > 0 else None
