"""Model FLOPs per token of a dense decoder, for the step's share of the
chip's peak (`mfu_int8.*`): the matmuls of every layer (2 * parameters),
attention's QK and PV over the token's keys, and the head for a token
whose logits are used (a decoded token, or a prompt's last token).
Counted against the int8 peak, the chip's highest, so the share is a
lower bound on how busy the step keeps the chip.
"""
from bench.costs import sparq_matmul

PEAK = "int8_ops"


def matmul_flops(s: dict) -> float:
    return sparq_matmul.rows(s, 1)[0]


def head_flops(s: dict) -> float:
    return 2.0 * s["d"] * s["vocab"]


def attn_flops(s: dict, keys: float) -> float:
    return 4.0 * keys * s["heads"] * s["head_dim"] * s["layers"]


def decode_step(s: dict, ctx) -> float:
    n = len(ctx)
    return n * (matmul_flops(s) + head_flops(s)) + attn_flops(s, sum(ctx))


def chunk(s: dict, pos, completed: int) -> float:
    keys = float(sum(int(p) + 1 for p in pos))
    return len(pos) * matmul_flops(s) + attn_flops(s, keys) \
        + completed * head_flops(s)
