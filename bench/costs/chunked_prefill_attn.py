"""Operations and bytes of chunked-prefill attention: each prompt token
of a chunk attends every key at or before its position, the history
below its segment start from packed §5.1 pages and the rest from the
chunk's own float K/V.

Ops: 4 * keys * heads * head_dim per token and layer, against the bf16
peak (`bf16_flops`). Bytes: each sequence's history once at the packed
bytes per value, plus per token its bf16 K and V (written by the chunk)
and its bf16 query and output.
"""
from bench.costs.sparq_format import KV_BYTES_PER_VALUE

PEAK = "bf16_flops"


def chunk(s: dict, pos, seqs):
    """(ops, bytes) of one chunk, all layers. `pos`: positions of the
    live tokens; `seqs`: (history, tokens) of each sequence in it."""
    H, KV, hd = s["heads"], s["kv_heads"], s["head_dim"]
    keys = float(sum(int(p) + 1 for p in pos))
    ops = 4.0 * keys * H * hd
    hist = float(sum(h for h, _ in seqs))
    toks = float(sum(n for _, n in seqs))
    nbytes = hist * 2 * KV * hd * KV_BYTES_PER_VALUE \
        + toks * (2 * KV * hd * 2 + 2 * H * hd * 2)
    return ops * s["layers"], nbytes * s["layers"]
