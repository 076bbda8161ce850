"""Operations and bytes of the SPARQ quantized matmul, x [M, K] (bf16
activations, quantized inside the kernel) times int8 weight codes
[K, N], with per-output-channel f32 scales and an f32 product.

Ops are counted against the chip's int8 peak (`int8_ops`): the products
run on int8 codes. M is the number of live rows (active decode slots,
or the prompt tokens of a chunk), never the padded tile.
"""
from bench.costs import layers

PEAK = "int8_ops"


def call(m: int, k: int, n: int):
    """(ops, bytes) of one call."""
    ops = 2.0 * m * k * n
    nbytes = k * n + 2.0 * m * k + 4.0 * m * n + 4.0 * n
    return ops, nbytes


def layer_shapes(s: dict, kind: str = "dense"):
    """(K, N) of every quantized matmul a token passes through in one
    layer of `kind` (`layers.groups`): the attention projections, then
    the dense FFN ("dense") or the shared experts ("moe").

    Latent attention's projections are `wq`, `w_dkv` and `wo`. Its
    `w_uk` and `w_uv` products, absorbed into attention, and the routed
    experts are costed with their own kernels
    (`costs/paged_latent_attn.py`, `costs/expert_matmul.py`)."""
    d = s["d"]
    if layers.latent(s):
        H, r = s["heads"], s["kv_lora_rank"]
        dn, dr, dv = s["qk_nope_dim"], s["qk_rope_dim"], s["v_head_dim"]
        shapes = [(d, H * (dn + dr)), (d, r + dr), (H * dv, d)]
    else:
        q, kv = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
        shapes = [(d, q), (d, kv), (d, kv), (q, d)]
    if kind == "dense":
        return shapes + layers.ffn(d, s["ff"], s["mlp"])
    if s.get("shared_experts"):
        shapes += layers.ffn(d, s["shared_experts"] * s["moe_ff"], s["mlp"])
    return shapes


def rows(s: dict, m: int):
    """(ops, bytes) of all quantized matmuls of every layer for m rows."""
    ops = nbytes = 0.0
    for n_layers, kind in layers.groups(s):
        o_layer = b_layer = 0.0
        for k, n in layer_shapes(s, kind):
            o, b = call(m, k, n)
            o_layer, b_layer = o_layer + o, b_layer + b
        ops, nbytes = ops + o_layer * n_layers, nbytes + b_layer * n_layers
    return ops, nbytes
