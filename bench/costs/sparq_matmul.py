"""Operations and bytes of the SPARQ quantized matmul, x [M, K] (bf16
activations, quantized inside the kernel) times int8 weight codes
[K, N], with per-output-channel f32 scales and an f32 product.

Ops are counted against the chip's int8 peak (`int8_ops`): the products
run on int8 codes. M is the number of live rows (active decode slots,
or the prompt tokens of a chunk), never the padded tile.
"""
PEAK = "int8_ops"


def call(m: int, k: int, n: int):
    """(ops, bytes) of one call."""
    ops = 2.0 * m * k * n
    nbytes = k * n + 2.0 * m * k + 4.0 * m * n + 4.0 * n
    return ops, nbytes


def layer_shapes(s: dict):
    """(K, N) of every quantized matmul in one layer of a dense model."""
    d, ff = s["d"], s["ff"]
    q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    shapes = [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (ff, d)]
    if s["mlp"] == "swiglu":
        shapes.append((d, ff))
    return shapes


def rows(s: dict, m: int):
    """(ops, bytes) of all quantized matmuls of every layer for m rows."""
    ops = nbytes = 0.0
    for k, n in layer_shapes(s):
        o, b = call(m, k, n)
        ops, nbytes = ops + o, nbytes + b
    return ops * s["layers"], nbytes * s["layers"]
