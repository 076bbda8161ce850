"""`cell.model_config`: every size a configuration file states reaches the
program's ModelConfig, and a size it cannot place raises."""
import dataclasses

import pytest

from bench import cell, tiny

TINY_MOE = tiny.MOE_CONF

#: the fields the harness set at the parent commit for each configuration
#: file; every other field is the registry entry's
DENSE = {
    "starcoder2-3b.decode-4k": dict(
        name="starcoder2-3b", n_layers=30, d_model=3072, n_heads=24,
        n_kv_heads=2, d_ff=12288, vocab_size=49152, mlp_type="gelu",
        norm_type="layernorm", rope_theta=999999.4420358813,
        tie_embeddings=True, norm_eps=1e-05),
    "mistral-large-123b-l4.offline-chat": dict(
        name="mistral-large-123b-l4", n_layers=4, d_model=12288,
        n_heads=96, n_kv_heads=8, d_ff=28672, vocab_size=32768,
        mlp_type="swiglu", norm_type="rmsnorm", rope_theta=1000000.0,
        tie_embeddings=False, norm_eps=1e-05),
}
#: a value for each mapped key, unlike the tiny file's and the registry's
VALUES = {
    "n_routed_experts": 16, "num_experts": 16, "num_experts_per_tok": 3,
    "moe_intermediate_size": 96, "n_shared_experts": 1,
    "first_k_dense_replace": 2, "kv_lora_rank": 48, "q_lora_rank": 64,
    "qk_nope_head_dim": 24, "qk_rope_head_dim": 8, "v_head_dim": 40,
    "norm_topk_prob": False, "routed_scaling_factor": 16.0,
    "rope_scaling": {"type": "yarn", "factor": 40, "mscale": 0.707,
                     "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "beta_fast": 32, "beta_slow": 1},
}


def _fields():
    from repro.models.common import ModelConfig
    return {f.name for f in dataclasses.fields(ModelConfig)}


@pytest.mark.parametrize("name", sorted(DENSE))
def test_dense_files_give_the_parents_config(name):
    from repro.configs.base import get_config
    conf = cell.load_spec(name).conf
    want = get_config(conf["registry"]).replace(**DENSE[name])
    assert cell.model_config(conf) == want


def test_every_mapped_key_is_covered():
    assert set(VALUES) == set(cell.MAPPED)


def test_moe_latent_file_sets_every_mapped_field():
    cfg = cell.model_config(TINY_MOE)
    for key, field in cell.MAPPED.items():
        if key in TINY_MOE:
            assert getattr(cfg, field) == TINY_MOE[key], key
    assert (cfg.family, cfg.n_layers, cfg.tie_embeddings) == ("moe", 3, False)


@pytest.mark.parametrize("key", sorted(VALUES))
def test_a_stated_key_reaches_its_field_or_raises(key):
    """A key whose field the program has sets it; one whose field it lacks
    raises, never dropped."""
    field = cell.MAPPED[key]
    conf = {k: v for k, v in TINY_MOE.items() if cell.MAPPED.get(k) != field}
    conf[key] = VALUES[key]
    if field in _fields():
        assert getattr(cell.model_config(conf), field) == VALUES[key]
    else:
        with pytest.raises(ValueError, match=field):
            cell.model_config(conf)


def test_a_null_key_states_nothing():
    cfg = cell.model_config(dict(TINY_MOE, q_lora_rank=None))
    assert cfg == cell.model_config(TINY_MOE)


def test_an_experts_cut_in_reduced_reaches_the_config():
    conf = dict(TINY_MOE, n_routed_experts=4, reduced=["n_routed_experts"],
                published={"n_routed_experts": 8})
    assert cell.model_config(conf).n_experts == 4


def test_two_keys_for_one_field_must_agree():
    assert cell.model_config(dict(TINY_MOE, num_experts=8)).n_experts == 8
    with pytest.raises(ValueError, match="n_experts"):
        cell.model_config(dict(TINY_MOE, num_experts=4))


@pytest.mark.parametrize("key", ["n_routed_experts", "num_experts_per_tok",
                                 "moe_intermediate_size", "n_shared_experts",
                                 "first_k_dense_replace", "kv_lora_rank",
                                 "qk_nope_head_dim", "qk_rope_head_dim",
                                 "v_head_dim"])
def test_a_size_left_to_the_registry_raises(key):
    """The registry's DeepSeek entry has experts and a latent rank; a file
    that does not state one of their sizes cannot leave it to the
    registry."""
    conf = {k: v for k, v in TINY_MOE.items() if k != key}
    with pytest.raises(ValueError, match=cell.MAPPED[key]):
        cell.model_config(conf)


def test_a_registry_moe_entry_with_no_expert_keys_raises():
    conf = {k: v for k, v in TINY_MOE.items() if k not in cell.MAPPED}
    with pytest.raises(ValueError, match="registry entry"):
        cell.model_config(conf)
