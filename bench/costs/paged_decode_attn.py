"""Operations and bytes of paged decode attention over packed §5.1 pages:
one query token per active sequence against its live context.

Ops: QK and PV, 4 * ctx * heads * head_dim per sequence and layer,
against the chip's bf16 peak (`bf16_flops`). Bytes: the live context's
K and V at the packed format's bytes per value (not the pool's, not
padding), plus the bf16 query read and output written.
"""
from bench.costs.sparq_format import KV_BYTES_PER_VALUE

PEAK = "bf16_flops"


def step(s: dict, ctx):
    """(ops, bytes) of one decode step, all layers; `ctx` lists the keys
    each active sequence attends (its position + 1)."""
    H, KV, hd = s["heads"], s["kv_heads"], s["head_dim"]
    tot = float(sum(ctx))
    ops = 4.0 * tot * H * hd
    nbytes = tot * 2 * KV * hd * KV_BYTES_PER_VALUE + len(ctx) * H * hd * 4
    return ops * s["layers"], nbytes * s["layers"]
