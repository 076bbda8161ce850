"""The benchmark's weight draw: expert banks drawn expert by expert from
keys of their own, the same values for the reference, and the dense
draws as they were."""
import hashlib

import jax
import numpy as np
import pytest

from bench import cell, tiny, weights

TINY_MOE = tiny.MOE_CONF
SEED = 2 ** 31 + 17
#: the std of a truncated normal cut at 2 std, over its nominal std
TRUNC = 0.8796256610342398

#: sha256 of the served tree of each cell's `tiny.spec`, as the harness
#: drew it before expert banks were drawn (leaf paths, dtypes, shapes and
#: bytes in tree order)
DENSE_SUMS = {
    "starcoder2-3b.decode-4k":
        "719cbba25156ffdf923c94d5a8a69c4278407f0e3d8a705d90f265fbde894bc0",
    "mistral-large-123b-l4.offline-chat":
        "e57f641fc887ba2a1e2906ad56fddf0b944e4c73df150516b038315adaa5eacd",
}


def _checksum(tree) -> str:
    h = hashlib.sha256()
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(kp)}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def moe():
    from repro.models.model import Model
    model = Model(cell.model_config(TINY_MOE))
    return model, weights.served_params(model, SEED)


def _banks(model):
    """(name, [L, E, d_in, d_out]) of every expert bank of the MoE group."""
    shapes = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    moe = shapes["blocks"][1]["moe"]
    return [(k, v.shape) for k, v in sorted(moe.items()) if len(v.shape) == 4]


def test_tiny_moe_has_expert_banks_and_latent_attention(moe):
    model, params = moe
    assert model.kinds == ["mla_dense", "mla_moe", "mla_moe"]
    banks = dict(_banks(model))
    assert banks["w_up"] == (2, 8, 128, 64)
    assert banks["w_down"] == (2, 8, 64, 128)
    assert banks["sh_up"] == (2, 1, 128, 128)
    assert set(params["blocks"][0]["attn"]) >= {"wq", "w_dkv", "w_uk",
                                                "w_uv", "wo"}


def test_no_expert_or_shared_leaf_is_zero(moe):
    _, params = moe
    for kp, a in jax.tree_util.tree_flatten_with_path(
            params["blocks"][1]["moe"])[0]:
        a = np.abs(np.asarray(a, np.float32))
        per = a.reshape(a.shape[0], a.shape[1], -1).max(-1)
        assert (per > 0).all(), jax.tree_util.keystr(kp)


def test_each_expert_std_follows_its_rule(moe):
    model, _ = moe
    draw = weights.expert_drawer(SEED)
    gain = {"w_down": weights.OUT_GAIN, "sh_down": weights.OUT_GAIN}
    for name, (L, E, d_in, d_out) in _banks(model):
        rule = d_in ** -0.5 * gain.get(name, 1.0) * TRUNC
        for layer in range(L):
            for e in range(E):
                w = np.asarray(draw(f"blocks/1/moe/{name}", layer, e,
                                    (d_in, d_out)), np.float32)
                assert abs(w.std() / rule - 1) < 0.05, (name, layer, e)


def test_experts_differ_from_each_other(moe):
    model, _ = moe
    draw = weights.expert_drawer(SEED)
    a, b = (np.asarray(draw("blocks/1/moe/w_up", 0, e, (128, 64)))
            for e in (0, 1))
    assert not np.array_equal(a, b)


def test_expert_drawer_gives_the_served_values(moe):
    """Each layer's bank, drawn expert by expert for the reference and put
    through the program's quantizer as the harness hands it, equals what
    the program was served, bit for bit."""
    from repro.models.quantize import quantize_params
    model, params = moe
    draw = weights.expert_drawer(SEED)
    for name, (L, E, d_in, d_out) in _banks(model):
        served = params["blocks"][1]["moe"][name]
        for layer in range(L):
            bank = np.stack([np.asarray(draw(f"blocks/1/moe/{name}", layer,
                                             e, (d_in, d_out)))
                             for e in range(E)])
            want = quantize_params({name: bank[None]})[name]
            got = jax.tree.map(lambda a: np.asarray(a[layer]), served)
            want = jax.tree.map(lambda a: np.asarray(a[0]), want)
            assert jax.tree.structure(got) == jax.tree.structure(want)
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                assert g.dtype == w.dtype and np.array_equal(g, w), \
                    (name, layer)


@pytest.mark.parametrize("name", sorted(DENSE_SUMS))
def test_dense_draws_are_unchanged(name):
    from repro.models.model import Model
    spec = tiny.spec(name)
    model = Model(cell.model_config(spec.conf))
    params = weights.served_params(model, spec.conf["weight_seed"])
    assert _checksum(params) == DENSE_SUMS[name]
