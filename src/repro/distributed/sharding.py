"""Sharding rules: FSDP (+ZeRO) over the data axes x tensor/expert
parallelism over the model axis, with sequence-parallel residual streams.

`param_pspecs` pattern-matches parameter names to PartitionSpecs and then
*fits* each spec to the actual shape (a mesh axis that does not divide the
corresponding dimension is dropped, e.g. whisper's 51865 vocab over a
16-way model axis). The same machinery produces optimizer-state, cache and
batch specs, so everything the step functions touch is covered.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig

TP = "model"


def dp_axes(mesh: Mesh, tensor_parallel: bool = True):
    """Data-parallel axes: ('pod','data') on the multi-pod mesh; with
    tensor parallelism off, the model axis joins the DP/FSDP group
    (pure ZeRO layout for models too small to TP over 16)."""
    names = mesh.axis_names
    dp = ("pod", "data") if "pod" in names else ("data",)
    return dp if tensor_parallel else dp + (TP,)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        out = 1
        for a in axis:
            out *= mesh.shape[a]
        return out
    return mesh.shape[axis]


def fit_spec(shape: tuple, spec: P, mesh: Mesh) -> P:
    """Drop mesh axes that do not evenly divide their dimension. For tuple
    entries (merged DP groups) try suffixes first: a batch of 256 on the
    512-chip ('pod','data','model') group falls back to ('data','model')
    instead of replicating (§Perf iteration 16)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, axis in zip(shape, entries):
        if isinstance(axis, (tuple, list)):
            fitted = None
            for i in range(len(axis)):
                cand = tuple(axis[i:])
                if dim > 0 and dim % _axis_size(mesh, cand) == 0:
                    fitted = cand if len(cand) > 1 else cand[0]
                    break
            out.append(fitted)
        elif axis is not None and dim > 0 and \
                dim % _axis_size(mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


# parameter-name -> base spec builders (dp = FSDP axes tuple)
def _rules(dp):
    col = P(dp, TP)        # column-parallel: [d_in, d_out-sharded]
    row = P(TP, dp)        # row-parallel:    [d_in-sharded, d_out]
    return {
        "embed": P(TP, dp),          # [vocab, d]
        "lm_head": col,              # [d, vocab]
        # attention
        "wq": col, "wk": col, "wv": col, "wo": row,
        # ffn
        "w_up": col, "w_gate": col, "w_down": row,
        # moe experts get 3-D handling below; router:
        "router": P(dp, None),
        "sh_up": P(None, dp, TP), "sh_gate": P(None, dp, TP),
        "sh_down": P(None, TP, dp),
        # mla
        "w_dkv": P(dp, None), "w_uk": P(None, TP), "w_uv": P(None, TP),
        # rwkv
        "w_r": col, "w_k": col, "w_v": col, "w_g": col, "w_o": row,
        "w_ck": col, "w_cr": col, "w_cv": row,
        "w_A": P(dp, None), "w_B": P(None, dp),
        # rg-lru
        "w_y": col, "w_x": col, "w_a": P(TP, None), "w_i": P(TP, None),
        "w_out": row, "conv_k": P(None, TP),
    }


_EXPERT_KEYS = ("w_up", "w_gate", "w_down")


def param_pspecs(params: Any, mesh: Mesh,
                 tensor_parallel: bool = True) -> Any:
    """PartitionSpec tree matching `params` (arrays or ShapeDtypeStructs)."""
    dp = dp_axes(mesh, tensor_parallel)
    rules = _rules(dp)
    if not tensor_parallel:  # ZeRO: shard first dim over everything
        rules = {k: P(dp) if len(v) and v[0] is not None else
                 (P(None, dp) if len(v) > 1 else P(dp))
                 for k, v in rules.items()}
        rules["embed"] = P(dp)
        rules["lm_head"] = P(dp)

    def spec_for(path, leaf) -> P:
        names = [k for k in (getattr(e, "key", getattr(e, "name", None))
                             for e in path) if isinstance(k, str)]
        name = names[-1] if names else None
        is_scale = False
        if name in ("q", "s") and len(names) >= 2:  # pre-quantized weight
            is_scale = name == "s"
            name = names[-2]
        shape = leaf.shape
        base = rules.get(name)
        if is_scale and base is not None:
            # per-output-channel scales [*, d_out]: keep only d_out's axis
            base = P(base[-1]) if len(base) else P()
        nd = len(shape)
        if base is None:
            base = P()          # norms, scalars, vectors: replicate
        elif name in _EXPERT_KEYS and nd == 4:
            # stacked MoE experts [L, E, din, dout]: EP over model +
            # FSDP over din (3-D w_up/w_gate/w_down are stacked *dense*
            # FFNs [L, din, dout] and take the layer rule below)
            base = P(None, TP, dp, None)
        elif nd == len(base) + 1:
            base = P(None, *base)        # stacked layers: leading L dim
        return fit_spec(shape, base, mesh)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def batch_pspecs(batch: Any, mesh: Mesh,
                 tensor_parallel: bool = True) -> Any:
    """Batch dim over all data axes; sequence unsharded at input."""
    dp = dp_axes(mesh, tensor_parallel)

    def spec_for(leaf):
        return fit_spec(leaf.shape, P(dp), mesh)

    return jax.tree.map(spec_for, batch)


def cache_pspecs(caches: Any, model, mesh: Mesh,
                 tensor_parallel: bool = True) -> Any:
    """Decode-cache specs. Leading dim is the stacked layer axis; batch
    over dp. KV time axes (dim 2 of [L,B,T,KV,hd]) shard over the model
    axis — flash-decoding style: QK^T contracts hd (unsharded), scores and
    the PV partial sums reduce over the sequence with tiny [B,H] "
    all-reduces instead of hd-partial score reductions (§Perf iteration 3).
    Falls back to the last dim, then batch-only, when T doesn't divide."""
    dp = dp_axes(mesh, tensor_parallel)

    def spec_for(leaf):
        shape = leaf.shape
        nd = len(shape)
        if nd <= 1:           # per-layer scalars (pos counters)
            return P()
        entries = [None] * nd
        entries[1] = dp
        if tensor_parallel and nd >= 4:
            entries[2] = TP                      # sequence axis
            spec = fit_spec(shape, P(*entries), mesh)
            if spec[2] is not None:
                return spec
            entries[2] = None
        if tensor_parallel and nd >= 3:
            entries[-1] = TP                     # state width fallback
        return fit_spec(shape, P(*entries), mesh)

    return jax.tree.map(spec_for, caches)


def shardings_of(tree: Any, specs: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def activation_spec(mesh: Mesh, sp: bool = False,
                    tensor_parallel: bool = True) -> P:
    """Residual-stream constraint [B, T, D]: batch over dp (+ sequence over
    model when sequence parallelism is on)."""
    dp = dp_axes(mesh, tensor_parallel)
    return P(dp, TP if (sp and tensor_parallel) else None, None)


# ---------------------------------------------------------------------
# activation-constraint hooks: launch code pins the mesh context before
# tracing; model code calls constrain()/constrain_heads() at boundaries.
# ---------------------------------------------------------------------
_ACT_SPEC: Optional[P] = None
_MESH: Optional[Mesh] = None
_TP: bool = True


def set_activation_spec(spec: Optional[P], mesh: Optional[Mesh] = None,
                        tensor_parallel: bool = True) -> None:
    global _ACT_SPEC, _MESH, _TP
    _ACT_SPEC = spec
    _MESH = mesh
    _TP = tensor_parallel


def constrain(x: jnp.ndarray) -> jnp.ndarray:
    """Residual stream [B, T, D] constraint at layer boundaries."""
    if _ACT_SPEC is None or x.ndim != 3:
        return x
    return jax.lax.with_sharding_constraint(x, _ACT_SPEC)


def constrain_axis(x: jnp.ndarray, candidates: tuple[int, ...]):
    """Pin batch (dim 0) over dp and the first *divisible* candidate axis
    over the model axis. Used to keep GSPMD from replicating big recurrent /
    blocked-attention intermediates across the model axis."""
    if _MESH is None:
        return x
    dp = dp_axes(_MESH, _TP)
    if not _TP:  # ZeRO mode: batch over everything, no model-axis use
        return jax.lax.with_sharding_constraint(
            x, fit_spec(x.shape, P(dp), _MESH))
    for ax in candidates:
        if ax >= x.ndim:
            continue
        entries = [None] * x.ndim
        entries[0] = dp
        entries[ax] = TP
        spec = fit_spec(x.shape, P(*entries), _MESH)
        if spec[ax] is not None:
            return jax.lax.with_sharding_constraint(x, spec)
    spec = fit_spec(x.shape, P(dp), _MESH)
    return jax.lax.with_sharding_constraint(x, spec)


def constrain_heads(x: jnp.ndarray) -> jnp.ndarray:
    """Pin [B, T, H, hd] attention tensors: batch over dp, heads over the
    model axis when divisible, else REPLICATED over model (batch-only).

    Never fall back to sharding head_dim: hd is the contraction dim of
    QK^T, and a contraction-sharded operand turns every flash score block
    into a partial-sum all-reduce (measured: 5.7 TB/device on
    starcoder2-3b prefill_32k — EXPERIMENTS.md §Perf iteration 1)."""
    if _MESH is None or x.ndim != 4:
        return x
    return constrain_axis(x, (2,))


def constrain_last(x: jnp.ndarray) -> jnp.ndarray:
    """Pin [B, T, W] width-major recurrent tensors (RG-LRU, token-shift)."""
    if _MESH is None or x.ndim != 3:
        return x
    return constrain_axis(x, (2,))


# ----------------------------------------------------------------------
# paged-serving pool specs (tensor-parallel ContinuousBatchingEngine)
# ----------------------------------------------------------------------

def pool_plane_pspec(ndim: int) -> P:
    """PartitionSpec for one packed §5.1 page-pool plane: the lane axis
    (always the last: [..., P, ps, KV*hd], KV-major) shards over the
    model axis, so each device holds KV/tp whole heads; everything else
    — pages, rows, an optional leading layer-stack axis — is replicated.
    Head groups never split because the engine validates
    n_kv_heads % tp == 0 up front."""
    entries = [None] * ndim
    entries[ndim - 1] = TP
    return P(*entries)


def pool_plane_sharding(mesh: Mesh, ndim: int) -> NamedSharding:
    return NamedSharding(mesh, pool_plane_pspec(ndim))


def paged_pool_pspecs(store) -> Any:
    """A PagedCacheStore-shaped pytree of PartitionSpecs: packed data and
    meta pools shard by KV head, bookkeeping (per-sequence scales, block
    tables, positions) stays replicated — the host-side allocator/prefix
    index/scheduler are global, so every device sees the same tables."""
    import dataclasses as _dc
    pools = {"k_data", "k_meta", "v_data", "v_meta"}
    specs = {name: (pool_plane_pspec(getattr(store, name).ndim)
                    if name in pools else P())
             for name in ("k_data", "k_meta", "v_data", "v_meta",
                          "k_scale", "v_scale", "block_table", "seq_pos")}
    return _dc.replace(store, **specs)


def paged_pool_shardings(store, mesh: Mesh) -> Any:
    """Same tree with NamedShardings — ready for `jax.device_put`."""
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        paged_pool_pspecs(store))


def constrain_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Megatron-SP re-entry point: gather the sequence axis back (batch-only
    sharding) before the TP matmuls of a block. Without this, GSPMD keeps
    the sequence on the model axis and full-gathers the *weights* instead —
    catastrophically worse (weights >> activations per microbatch)."""
    if _MESH is None or x.ndim != 3:
        return x
    dp = dp_axes(_MESH, _TP)
    return jax.lax.with_sharding_constraint(
        x, fit_spec(x.shape, P(dp, None, None), _MESH))
