"""Operations and bytes of paged latent attention (MLA in its absorbed
form) over packed §5.1 latent pages: each query attends its sequence's
live positions, each a latent c_kv of `kv_lora_rank` values and a rope
key of `qk_rope_dim` shared by all heads.

Ops: per key and layer, the scores 2 * heads * (r + dr) and the
latent-space PV 2 * heads * r, against the bf16 peak (`bf16_flops`):
the least work a latent cache admits (decompressing K and V per head
does more). Bytes: each sequence's live latent positions (not the
pool's, not padding) at the packed format's bytes per value, read once
for all heads; per query its bf16 latent query (heads * (r + dr)) read
and latent output (heads * r) written; in a chunk, each new token's
bf16 latent written too.
"""
from bench.costs.sparq_format import KV_BYTES_PER_VALUE

PEAK = "bf16_flops"


def per_key(s: dict) -> float:
    """FLOPs per key and layer."""
    H, r, dr = s["heads"], s["kv_lora_rank"], s["qk_rope_dim"]
    return 2.0 * H * (r + dr) + 2.0 * H * r


def _query_bytes(s: dict) -> float:
    """A query's bf16 latent query read and latent output written."""
    H, r, dr = s["heads"], s["kv_lora_rank"], s["qk_rope_dim"]
    return 2.0 * (H * (r + dr) + H * r)


def step(s: dict, ctx):
    """(ops, bytes) of one decode step, all layers; `ctx` lists the keys
    each active sequence attends (its position + 1)."""
    width = s["kv_lora_rank"] + s["qk_rope_dim"]
    tot = float(sum(ctx))
    ops = per_key(s) * tot
    nbytes = tot * width * KV_BYTES_PER_VALUE + len(ctx) * _query_bytes(s)
    return ops * s["layers"], nbytes * s["layers"]


def chunk(s: dict, pos, seqs):
    """(ops, bytes) of one chunk, all layers. `pos`: positions of the
    live tokens; `seqs`: (history, tokens) of each sequence in it."""
    width = s["kv_lora_rank"] + s["qk_rope_dim"]
    keys = float(sum(int(p) + 1 for p in pos))
    hist = float(sum(h for h, _ in seqs))
    toks = float(sum(n for _, n in seqs))
    ops = per_key(s) * keys
    nbytes = hist * width * KV_BYTES_PER_VALUE \
        + toks * (2.0 * width + _query_bytes(s))
    return ops * s["layers"], nbytes * s["layers"]
