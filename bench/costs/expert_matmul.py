"""Operations and bytes of one MoE layer's routed experts: each expert's
FFN of width `moe_ff` (up, down, and the gate with SwiGLU) over the rows
routed to it, on int8 codes with per-output-channel f32 scales.

Ops are exact from the rows each expert received: 2 * rows * K * N per
matrix, against the int8 peak (`int8_ops`). Bytes are the least a kernel
moves: the codes and scales of each expert that received a row, read
once (an expert no row reached costs nothing), and each routed row's
bf16 input read and bf16 output written. The shared experts are not
here: they run for every token, in `sparq_matmul.layer_shapes`.
"""
from bench.costs import layers

PEAK = "int8_ops"


def layer(s: dict, rows):
    """(ops, bytes) of one MoE layer's routed experts; `rows[e]` is the
    number of rows (token, expert) routed to expert e."""
    d = s["d"]
    shapes = layers.ffn(d, s["moe_ff"], s["mlp"])
    weights = sum(k * n + 4.0 * n for k, n in shapes)
    params = sum(k * n for k, n in shapes)
    ops = nbytes = 0.0
    for m in rows:
        if m > 0:
            ops += 2.0 * m * params
            nbytes += weights + 4.0 * m * d
    return ops, nbytes
