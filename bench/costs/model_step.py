"""Model FLOPs per token of a decoder, for the step's share of the
chip's peak (`mfu_int8.*`): the matmuls of every layer (2 * parameters),
attention over the token's keys, and the head for a token whose logits
are used (a decoded token, or a prompt's last token). Counted against
the int8 peak, the chip's highest, so the share is a lower bound on how
busy the step keeps the chip.

In a model with sparse experts (`costs/layers.py`), an MoE layer counts
its `experts_active` routed experts and its shared experts, a dense
layer its dense FFN; the router's small matmul is left out. Latent
attention counts its projections, the `w_uk` and `w_uv` products (one
of each per token, absorbed or not), and per key the absorbed form's
work (`costs/paged_latent_attn.py`), the least a latent cache admits.
"""
from bench.costs import layers, paged_latent_attn, sparq_matmul

PEAK = "int8_ops"


def matmul_flops(s: dict) -> float:
    flops = sparq_matmul.rows(s, 1)[0]
    if layers.latent(s):
        H, r = s["heads"], s["kv_lora_rank"]
        flops += 2.0 * H * r * (s["qk_nope_dim"] + s["v_head_dim"]) \
            * s["layers"]
    for n_layers, kind in layers.groups(s):
        if kind == "moe":
            expert = sum(k * n for k, n in
                         layers.ffn(s["d"], s["moe_ff"], s["mlp"]))
            flops += 2.0 * expert * s["experts_active"] * n_layers
    return flops


def head_flops(s: dict) -> float:
    return 2.0 * s["d"] * s["vocab"]


def attn_flops(s: dict, keys: float) -> float:
    if layers.latent(s):
        return paged_latent_attn.per_key(s) * keys * s["layers"]
    return 4.0 * keys * s["heads"] * s["head_dim"] * s["layers"]


def decode_step(s: dict, ctx) -> float:
    n = len(ctx)
    return n * (matmul_flops(s) + head_flops(s)) + attn_flops(s, sum(ctx))


def chunk(s: dict, pos, completed: int) -> float:
    keys = float(sum(int(p) + 1 for p in pos))
    return len(pos) * matmul_flops(s) + attn_flops(s, keys) \
        + completed * head_flops(s)
