"""Seeded jaxpr-auditor violations — one program per JX check ID.

Each function below, registered as a ProgramSpec by tests/test_analysis.py,
trips exactly one check and nothing else; the test asserts the exact
finding multiset so a dead check (or a check firing twice) is loud.
These are traced abstractly only — never executed.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sink(x):  # pragma: no cover - host side of the seeded callback
    del x


def hostcall(x):
    """JX101: a host callback inside a hot program."""
    jax.debug.callback(_sink, x)
    return x + 1


def packed_cast(codes):
    """JX102: packed int8 codes decoded to float outside any kernel.

    `codes` is an int8 plane; the astype is the stray full-plane
    materialization the packed format forbids on the hot path."""
    return codes.astype(jnp.float32) * 0.5


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def tile_misdivide(x):
    """JX103: the input block (32, 16) does not divide x's (48, 16)."""
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct((64, 16), x.dtype),
        grid=(2,),
        in_specs=[pl.BlockSpec((32, 16), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((32, 16), lambda i: (i, 0)),
        interpret=True)(x)


def _decode_kernel(p_ref, o_ref):
    # float conversion *inside* the kernel: legal (not JX102)
    o_ref[...] = p_ref[...].astype(jnp.float32)


def page_tile_mismatch(planes):
    """JX104: lane-dense int8 pool [P, ps, KV*hd] tiled at 8 rows/page in
    a program whose spec declares page_size=16 — the paged read no longer
    aligns to pages."""
    return pl.pallas_call(
        _decode_kernel,
        out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.float32),
        grid=(4, 2),
        in_specs=[pl.BlockSpec((1, 8, 16), lambda i, j: (i, j, 0))],
        out_specs=pl.BlockSpec((1, 8, 16), lambda i, j: (i, j, 0)),
        interpret=True)(planes)


def page_head_split(planes):
    """JX104 again: whole pages, but each block takes half the lane axis
    (one head of two) — a head-split tile the TPU tiling rule refuses."""
    return pl.pallas_call(
        _decode_kernel,
        out_shape=jax.ShapeDtypeStruct(planes.shape, jnp.float32),
        grid=(4, 2),
        in_specs=[pl.BlockSpec((1, 16, 8), lambda i, j: (i, 0, j))],
        out_specs=pl.BlockSpec((1, 16, 8), lambda i, j: (i, 0, j)),
        interpret=True)(planes)


def vmem_hog(x):
    """JX105 (under a small test budget): whole-array blocks."""
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        grid=(1,),
        in_specs=[pl.BlockSpec(x.shape, lambda i: (0, 0))],
        out_specs=pl.BlockSpec(x.shape, lambda i: (0, 0)),
        interpret=True)(x)


def shape_polymorphic(x):
    """JX106 when registered with a two-length shape set: one jit
    signature per length, i.e. the per-shape retrace JX106 forbids."""
    return x * 2


def shard_map_hostcall(x):
    """JX101 again, but buried inside a shard_map body: the auditor must
    walk through the shard_map eqn's inner jaxpr (the tensor-parallel
    decode/prefill programs all trace through one), not just pjit cores.
    A 1-device mesh keeps the fixture traceable on any host."""
    from jax.sharding import PartitionSpec as P
    mesh = jax.make_mesh((1,), ("model",))

    def body(v):
        jax.debug.callback(_sink, v)
        return v + 1

    return jax.shard_map(body, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(x)
