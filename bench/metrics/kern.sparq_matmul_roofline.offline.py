"""The SPARQ quantized matmul's share of its roofline, in percent: the
least time the chip needs for the matmuls of the decode steps and
prefill chunks that ran in the traced window (`costs/sparq_matmul.py`,
per call the larger of ops over the int8 peak and bytes over HBM
bandwidth), over the device time of its kernel there."""
from bench.costs import layers
from bench.costs import sparq_matmul as cost
from bench.observe import least_time, per_execution


def least(obs, m):
    s, p = obs.sizes, obs.peaks
    total = 0.0
    for n_layers, kind in layers.groups(s):
        t = 0.0
        for k, n in cost.layer_shapes(s, kind):
            ops, nbytes = cost.call(m, k, n)
            t += least_time(ops, nbytes, p[cost.PEAK], p["hbm_bytes_per_s"])
        total += t * n_layers
    return total


def read(obs):
    tr = obs.trace
    secs = (tr.kernel_ns.get(("step", "sparq_matmul"), 0.0)
            + tr.kernel_ns.get(("chunk", "sparq_matmul"), 0.0)) * 1e-9
    if secs <= 0:
        return None
    need = per_execution([least(obs, len(s.ctx)) for s in obs.steps_in()],
                         tr.module_count("step"))
    need += per_execution([least(obs, len(c.pos)) for c in obs.chunks_in()],
                          tr.module_count("chunk"))
    return 100.0 * need / secs if need > 0 else None
