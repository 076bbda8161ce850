"""The `sizes` vocabulary the cost functions read, and the layer kinds of
a configuration.

A reference's `sizes(conf)` gives, for a dense decoder: `layers`, `d`,
`heads`, `kv_heads`, `head_dim`, `ff` (the dense FFN's width), `vocab`
and `mlp` ("swiglu" or "gelu"). A configuration with sparse experts adds
`dense_layers` (how many leading layers keep the dense FFN), `experts`
(routed experts held), `experts_active` (routed experts a token runs
here: the published top-k where every expert is held, its share where
the chip holds a share), `shared_experts` and `moe_ff` (one expert's
width; the shared experts run as one FFN of width shared_experts *
moe_ff). One with latent attention (MLA) adds `kv_lora_rank`,
`qk_nope_dim`, `qk_rope_dim` and `v_head_dim`; its `heads` are the query
heads, and `kv_heads` and `head_dim` go unread. Sizes without those keys
are a dense decoder's, and every cost comes out as it did before the
keys existed.
"""
from typing import List, Tuple


def groups(s: dict) -> List[Tuple[int, str]]:
    """(layers, kind) for each run of layers: "dense" layers carry the
    dense FFN, "moe" layers the routed and shared experts."""
    if not s.get("experts"):
        return [(s["layers"], "dense")]
    runs = ((s["dense_layers"], "dense"),
            (s["layers"] - s["dense_layers"], "moe"))
    return [(n, kind) for n, kind in runs if n]


def latent(s: dict) -> bool:
    """Whether attention is latent (MLA)."""
    return bool(s.get("kv_lora_rank"))


def ffn(d: int, ff: int, mlp: str) -> List[Tuple[int, int]]:
    """(K, N) of an FFN's matmuls: up, down, and the gate with SwiGLU."""
    shapes = [(d, ff), (ff, d)]
    if mlp == "swiglu":
        shapes.append((d, ff))
    return shapes
