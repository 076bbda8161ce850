"""Chunked-prefill attention's share of its roofline, in percent: the
least time for the chunks in the traced window (`costs/
chunked_prefill_attn.py`: each token's live keys, history at 0.9375
B/value), over the device time of the `sparq_chunked_prefill_attn`
kernel there."""
from bench.costs import chunked_prefill_attn as cost
from bench.observe import least_time, per_execution


def read(obs):
    tr, p = obs.trace, obs.peaks
    secs = tr.kernel_ns.get(("chunk", "sparq_chunked_prefill_attn"),
                            0.0) * 1e-9
    if secs <= 0:
        return None
    L = obs.sizes["layers"]
    need = []
    for c in obs.chunks_in():
        ops, nbytes = cost.chunk(obs.sizes, c.pos, c.seqs)
        # one kernel call per layer, each its own share of the chunk
        need.append(L * least_time(ops / L, nbytes / L, p[cost.PEAK],
                                   p["hbm_bytes_per_s"]))
    total = per_execution(need, tr.module_count("chunk"))
    return 100.0 * total / secs if total > 0 else None
