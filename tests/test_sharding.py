"""Unit tests for distributed/sharding.py spec fitting and the
error-feedback gradient compressor.

`fit_spec` only ever touches `mesh.shape` (a name->size mapping), so a
duck-typed FakeMesh lets the whole grid run on a single CPU device with
arbitrary pretend topologies. The property tests follow the repo's
hypothesis-optional convention: hypothesis drives them when installed,
and a deterministic sweep covers the same invariants when it is not.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.collectives import GradCompressor, sparq_compress
from repro.distributed.sharding import (fit_spec, paged_pool_pspecs,
                                        pool_plane_pspec)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare CI images
    HAVE_HYPOTHESIS = False


@dataclasses.dataclass
class FakeMesh:
    """fit_spec/_axis_size only read mesh.shape[name]."""
    shape: dict


MESH = FakeMesh({"pod": 2, "data": 8, "model": 16})


# ----------------------------------------------------------------------
# fit_spec: axis dropping, tuple-suffix fallback, shape/spec zip edges
# ----------------------------------------------------------------------

class TestFitSpec:
    @pytest.mark.parametrize("shape,spec,want", [
        # divisible: spec survives untouched
        ((256, 1024), P("data", "model"), P("data", "model")),
        # 51865 % 16 != 0: the model axis is dropped, not rounded
        ((51865, 768), P("model", "data"), P(None, "data")),
        # both axes non-divisible
        ((7, 9), P("data", "model"), P(None, None)),
        # None entries pass through
        ((64, 100, 32), P("data", None, "model"), P("data", None, "model")),
    ])
    def test_axis_dropping_grid(self, shape, spec, want):
        assert fit_spec(shape, spec, MESH) == want

    @pytest.mark.parametrize("dim,want", [
        (512, ("pod", "data", "model")),   # 2*8*16=256 divides 512
        (256, ("pod", "data", "model")),
        (128, ("data", "model")),          # 256 no, 8*16=128 yes
        (16, "model"),                     # only the last singleton fits
        (8, None),                         # nothing fits -> replicate
    ])
    def test_tuple_suffix_dp_fallback(self, dim, want):
        """Merged DP groups degrade suffix-by-suffix instead of jumping
        straight to replication; a single-name suffix is unwrapped from
        its tuple."""
        spec = fit_spec((dim, 64), P(("pod", "data", "model"), None), MESH)
        assert spec == P(want, None)

    def test_spec_shorter_than_shape_pads_none(self):
        assert fit_spec((64, 32, 16, 8), P("data"), MESH) == \
            P("data", None, None, None)

    def test_empty_spec_on_any_rank(self):
        assert fit_spec((3, 4, 5), P(), MESH) == P(None, None, None)

    def test_zero_dim_never_sharded(self):
        # dim > 0 guard: 0 % n == 0 numerically, but an empty dim must
        # not claim a mesh axis
        assert fit_spec((0, 64), P("data", "model"), MESH) == \
            P(None, "model")
        assert fit_spec((0,), P(("pod", "data"),), MESH) == P(None)

    if HAVE_HYPOTHESIS:
        @given(dim=st.integers(0, 4096),
               axes=st.lists(st.sampled_from(["pod", "data", "model"]),
                             min_size=1, max_size=3, unique=True))
        @settings(max_examples=200, deadline=None)
        def test_property_fitted_spec_always_divides(self, dim, axes):
            self._check_divides(dim, tuple(axes))
    else:
        def test_property_fitted_spec_always_divides_fallback(self):
            """Deterministic sweep mirroring the hypothesis property."""
            groups = [("pod",), ("data",), ("model",),
                      ("pod", "data"), ("data", "model"),
                      ("pod", "data", "model")]
            for dim in list(range(0, 64)) + [100, 128, 255, 256, 51865]:
                for axes in groups:
                    self._check_divides(dim, axes)

    @staticmethod
    def _check_divides(dim, axes):
        spec = fit_spec((dim,), P(axes), MESH)
        fitted = spec[0]
        if fitted is None:
            return
        names = fitted if isinstance(fitted, tuple) else (fitted,)
        size = 1
        for a in names:
            size *= MESH.shape[a]
        assert dim > 0 and dim % size == 0
        # the fitted group is always a suffix of the requested one
        assert tuple(names) == tuple(axes[len(axes) - len(names):])


# ----------------------------------------------------------------------
# paged-pool specs (TP serving)
# ----------------------------------------------------------------------

class TestPoolSpecs:
    def test_plane_pspec_targets_kv_head_axis(self):
        # lane-dense plane [P, ps, KV*hd] and stacked [L, P, ps, KV*hd]:
        # the KV-major lane axis shards, so each device holds whole heads
        assert pool_plane_pspec(3) == P(None, None, "model")
        assert pool_plane_pspec(4) == P(None, None, None, "model")

    def test_store_tree_pools_shard_bookkeeping_replicated(self):
        from repro.launch.serve import ContinuousBatchingEngine  # noqa: F401
        from repro.models.paging import PagedCacheStore
        from repro.models.cache import CacheConfig
        from repro.core.sparq import SparqConfig

        cc = CacheConfig.sparq_cache(SparqConfig.opt5(signed=True),
                                     impl="reference")
        store = jax.eval_shape(
            lambda: PagedCacheStore.init(
                n_seqs=2, n_pages=8, page_size=4, n_blocks=4,
                kv_heads=2, head_dim=16, cc=cc))
        specs = paged_pool_pspecs(store)
        for name in ("k_data", "k_meta", "v_data", "v_meta"):
            plane = getattr(store, name)
            spec = getattr(specs, name)
            assert spec[plane.ndim - 1] == "model"
            assert all(s is None for i, s in enumerate(spec)
                       if i != plane.ndim - 1)
        for name in ("k_scale", "v_scale", "block_table", "seq_pos"):
            assert getattr(specs, name) == P()


# ----------------------------------------------------------------------
# GradCompressor: error feedback
# ----------------------------------------------------------------------

def _grads():
    k = jax.random.PRNGKey(0)
    return {
        "big": jax.random.normal(k, (128, 64), jnp.float32),   # 8192 elems
        "tiny": jnp.arange(8, dtype=jnp.float32) - 3.5,        # < min_size
    }


class TestGradCompressor:
    def test_residual_carries_quantization_error(self):
        comp = GradCompressor(bits=4, min_size=4096)
        g = _grads()
        state = comp.init(g)
        assert jnp.all(state["big"] == 0) and jnp.all(state["tiny"] == 0)
        c, resid = comp.compress(g, state)
        # compressed + residual reconstructs the target exactly
        assert jnp.allclose(c["big"] + resid["big"], g["big"],
                            atol=1e-6)
        # the compressor really did quantize (lossy on gaussian data)
        assert float(jnp.max(jnp.abs(resid["big"]))) > 0

    def test_small_leaf_exact_with_zero_residual(self):
        comp = GradCompressor(bits=4, min_size=4096)
        g = _grads()
        c, resid = comp.compress(g, comp.init(g))
        assert jnp.array_equal(c["tiny"], g["tiny"])
        assert jnp.all(resid["tiny"] == 0)

    def test_error_feedback_is_unbiased_over_steps(self):
        """Feeding the residual back makes the *sum* of transmitted
        gradients track the sum of true gradients: after N identical
        steps, sum(compressed) + final_residual == N * g."""
        comp = GradCompressor(bits=4, min_size=4096)
        g = _grads()
        state = comp.init(g)
        total = jnp.zeros_like(g["big"])
        for _ in range(5):
            c, state = comp.compress(g, state)
            total = total + c["big"]
        assert jnp.allclose(total + state["big"], 5.0 * g["big"],
                            atol=1e-4)

    def test_residual_matches_sparq_compress_directly(self):
        comp = GradCompressor(bits=4, min_size=4096)
        g = _grads()
        state = comp.init(g)
        # second step: target = g + residual, residual = target - Q(target)
        _, state = comp.compress(g, state)
        c2, resid2 = comp.compress(g, state)
        target = g["big"] + state["big"]
        want = sparq_compress(target, 4)
        assert jnp.allclose(c2["big"], want, atol=1e-6)
        assert jnp.allclose(resid2["big"], target - want, atol=1e-6)
