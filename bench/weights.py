"""Seeded weights, drawn on the device.

The benchmark owns the draw: every weight is a function of the
configuration's weight seed, the leaf's path and its layer alone, so the
reference can draw one layer at a time and get the very values the
program was given. Matmul weights are truncated normals of std
1/sqrt(d_in), the attention and MLP output projections OUT_GAIN times
that and the query and key projections QK_GAIN times; the embedding and
head std 0.02; norms at their identity values; all in bf16, the type
they are drawn in before the program quantizes them.

Why OUT_GAIN: with the usual depth-scaled init, the token's own
embedding dominates the residual stream, and a tied head then ranks the
input token first at every position (a repeat-token model: measured at
StarCoder2-3B's widths, the repeated token was the reference's best at
every position). Its served tokens would not depend on the context or
on any layer, and no check could see a fault in either. With the blocks
writing eight times more strongly than 1/sqrt(d_in), they dominate the
stream as in a trained model. Why QK_GAIN: at 1/sqrt(d_in) attention
scores have std 1 and each query spreads its weight over its whole
context, so what a layer reads from the cache hardly moves its output: a
decode step that dropped its own K/V moved the widest logit gap from
0.05 to 0.09 at a small size. At twice that (score std 4, attention as
peaked as a trained model's) it moved it to 1.5.

`served_params` builds the tree the program serves in one jitted call:
each stacked matmul weight is drawn layer by layer and handed to the
program's own `quantize_params`, so no float copy of all layers ever
exists at once.
"""
from __future__ import annotations

import zlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

#: leaves whose output feeds the residual stream
OUT_PROJ = ("wo", "w_down")
OUT_GAIN = 8.0
#: the query and key projections (sharper attention)
QK_PROJ = ("wq", "wk")
QK_GAIN = 2.0


def base_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (folded 32 bits at a time)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    seed >>= 32
    while seed:
        key = jax.random.fold_in(key, seed & 0xFFFFFFFF)
        seed >>= 32
    return key


def leaf_key(key, path: str, layer) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def draw_matrix(key, path: str, layer, shape: Tuple[int, int]
                ) -> jnp.ndarray:
    """One layer's [d_in, d_out] matmul weight, bf16."""
    std = shape[0] ** -0.5
    name = path.rsplit("/", 1)[-1]
    if name in OUT_PROJ:
        std *= OUT_GAIN
    elif name in QK_PROJ:
        std *= QK_GAIN
    z = jax.random.truncated_normal(leaf_key(key, path, layer), -2.0, 2.0,
                                    shape, jnp.float32)
    return (z * std).astype(jnp.bfloat16)


def draw_table(key, path: str, shape) -> jnp.ndarray:
    """Embedding [V, d] or untied head [d, V], bf16, std 0.02."""
    z = jax.random.truncated_normal(leaf_key(key, path, 0), -2.0, 2.0,
                                    shape, jnp.float32)
    return (z * 0.02).astype(jnp.bfloat16)


def norm_value(name: str, kind: str, shape) -> jnp.ndarray:
    """Identity norm parameters, f32: the program's rmsnorm multiplies by
    (1 + scale), so its scale is 0; layernorm's scale is 1, its bias 0."""
    one = name == "scale" and kind == "layernorm"
    return jnp.full(shape, 1.0 if one else 0.0, jnp.float32)


def _path(kp) -> str:
    parts = []
    for e in kp:
        k = getattr(e, "key", getattr(e, "idx", getattr(e, "name", None)))
        parts.append(str(k))
    return "/".join(parts)


def served_params(model, seed: int) -> Dict:
    """The program's parameter tree for `model`, drawn from `seed` and
    quantized by the program, built on the device in one jitted call."""
    return jax.jit(served_builder(model))(base_key(seed))


def served_builder(model) -> Callable:
    """key -> the served parameter tree (see `served_params`)."""
    from repro.models.quantize import quantize_params
    cfg = model.cfg
    shapes = jax.eval_shape(
        lambda: model.init_params(jax.random.PRNGKey(0), jnp.bfloat16))

    def build(key):
        def leaf(kp, sd):
            path = _path(kp)
            name = path.rsplit("/", 1)[-1]
            if path.startswith("blocks/") and len(sd.shape) == 3:
                def one(layer):
                    w = draw_matrix(key, path, layer, sd.shape[1:])
                    return quantize_params({name: w[None]})[name]
                out = jax.lax.map(one, jnp.arange(sd.shape[0]))
                return jax.tree.map(lambda a: a[:, 0], out)
            if name in ("embed", "lm_head"):
                return draw_table(key, path, sd.shape)
            return norm_value(name, cfg.norm_type, sd.shape)
        return jax.tree_util.tree_map_with_path(leaf, shapes)
    return build


def layer_drawer(seed: int) -> Callable:
    """For the reference: `draw(path, layer, shape)` -> the bf16
    [d_in, d_out] weight of one layer, the very values `served_params`
    handed to the program's quantizer."""
    key = base_key(seed)
    jitted: Dict[Tuple[str, Tuple[int, int]], Callable] = {}

    def draw(path: str, layer: int, shape: Tuple[int, int]) -> jnp.ndarray:
        fn = jitted.get((path, shape))
        if fn is None:
            fn = jax.jit(lambda l, p=path, s=tuple(shape):
                         draw_matrix(key, p, l, s))
            jitted[(path, shape)] = fn
        return fn(jnp.int32(layer))
    return draw


def table_drawer(seed: int) -> Callable:
    """For the reference: `draw(path, shape)` -> the bf16 embedding or
    head the program was given."""
    key = base_key(seed)
    return lambda path, shape: jax.jit(
        lambda: draw_table(key, path, tuple(shape)))()
