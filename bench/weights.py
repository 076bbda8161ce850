"""Seeded weights, drawn on the device.

The benchmark owns the draw: every weight is a function of the
configuration's weight seed, the leaf's path, its layer and (in an
expert bank) its expert alone, so the reference can draw one layer or
expert at a time and get the very values the program was given. Matmul
weights are truncated normals of std 1/sqrt(d_in), the attention and
MLP output projections OUT_GAIN times that and the query and key
projections QK_GAIN times; the embedding and head std 0.02; norms at
their identity values; all in bf16, the type they are drawn in before
the program quantizes them.

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

Latent attention (MLA) scores q_nope . (rms(c_kv) @ w_uk) + q_pe . k_pe,
where c_kv and k_pe come from w_dkv; the norm on c_kv cancels w_dkv's
scale in the first term but not in the rope term. Measured over the
first layer's 256 x 256 scores of seeded tokens (truncation at 2 std
makes each factor 0.88 of its nominal std): the dense cells' scores have
std 3.10 (width 128, 4 heads of 32) and 3.09 (StarCoder2-3B's widths).
MLA at width 128 (4 heads, rank 32, nope 32, rope 16) and at
DeepSeek-V2-Lite's widths (2048, 16 heads, rank 512, nope 128, rope 64)
reads 1.55 and 1.55 with the gain on `wq` alone, 2.65 and 2.68 with it
on `w_uk` too, and 3.09 and 3.09 with it on `w_uk` and `w_dkv`: so both
carry QK_GAIN.

Expert banks [E, d_in, d_out] are drawn one layer at a time like any
other stacked weight, each expert from a key of its own.

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
OUT_PROJ = ("wo", "w_down", "sh_down")
OUT_GAIN = 8.0
#: the query and key projections (sharper attention); in latent attention
#: the key side is the latent's down- and up-projections
QK_PROJ = ("wq", "wk", "w_uk", "w_dkv")
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


def _gain(path: str) -> float:
    name = path.rsplit("/", 1)[-1]
    if name in OUT_PROJ:
        return OUT_GAIN
    if name in QK_PROJ:
        return QK_GAIN
    return 1.0


def _normal(k, path: str, shape: Tuple[int, int]) -> jnp.ndarray:
    std = shape[0] ** -0.5 * _gain(path)
    z = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
    return (z * std).astype(jnp.bfloat16)


def draw_matrix(key, path: str, layer, shape: Tuple[int, int]
                ) -> jnp.ndarray:
    """One layer's [d_in, d_out] matmul weight, bf16."""
    return _normal(leaf_key(key, path, layer), path, shape)


def draw_expert(key, path: str, layer, expert, shape: Tuple[int, int]
                ) -> jnp.ndarray:
    """One expert's [d_in, d_out] weight in one layer's bank, bf16: each
    expert has a key of its own, folded from its layer's."""
    return _normal(jax.random.fold_in(leaf_key(key, path, layer), expert),
                   path, shape)


def draw_bank(key, path: str, layer, shape: Tuple[int, int, int]
              ) -> jnp.ndarray:
    """One layer's [E, d_in, d_out] expert bank, bf16."""
    return jax.vmap(lambda e: draw_expert(key, path, layer, e, shape[1:]))(
        jnp.arange(shape[0]))


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
            if path.startswith("blocks/") and len(sd.shape) in (3, 4):
                draw = draw_matrix if len(sd.shape) == 3 else draw_bank

                def one(layer):
                    w = draw(key, path, layer, sd.shape[1:])
                    return quantize_params({name: w[None]})[name]
                out = jax.lax.map(one, jnp.arange(sd.shape[0]))
                return jax.tree.map(lambda a: a[:, 0], out)
            if name in ("embed", "lm_head"):
                return draw_table(key, path, sd.shape)
            return norm_value(name, cfg.norm_type, sd.shape)
        return jax.tree_util.tree_map_with_path(leaf, shapes)
    return build


def _drawer(seed: int, fn: Callable) -> Callable:
    """`draw(path, shape, *indices)` -> fn(key, path, *indices, shape),
    one jitted program per (path, shape)."""
    key = base_key(seed)
    jitted: Dict[Tuple[str, Tuple[int, ...]], Callable] = {}

    def draw(path: str, shape, *indices) -> jnp.ndarray:
        shape = tuple(shape)
        f = jitted.get((path, shape))
        if f is None:
            f = jax.jit(lambda *i, p=path, s=shape: fn(key, p, *i, s))
            jitted[(path, shape)] = f
        return f(*(jnp.int32(i) for i in indices))
    return draw


def layer_drawer(seed: int) -> Callable:
    """For the reference: `draw(path, layer, shape)` -> the bf16
    [d_in, d_out] weight of one layer, the very values `served_params`
    handed to the program's quantizer."""
    draw = _drawer(seed, draw_matrix)
    return lambda path, layer, shape: draw(path, shape, layer)


def expert_drawer(seed: int) -> Callable:
    """For the reference: `draw(path, layer, expert, shape)` -> the bf16
    [d_in, d_out] weight of one expert of one layer's bank, the very
    values `served_params` handed to the program's quantizer."""
    draw = _drawer(seed, draw_expert)
    return lambda path, layer, expert, shape: draw(path, shape, layer,
                                                   expert)


def table_drawer(seed: int) -> Callable:
    """For the reference: `draw(path, shape)` -> the bf16 embedding or
    head the program was given."""
    key = base_key(seed)
    return lambda path, shape: jax.jit(
        lambda: draw_table(key, path, tuple(shape)))()
