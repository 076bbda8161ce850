"""A cell cut to a size the CPU test run holds: the same harness, engine
flags, traffic shapes and check, at a 2-layer model of width 128."""
from __future__ import annotations

from bench import cell

#: the check's limit at this size, set as a cell's is: between the
#: program's mean logit gap at this size (0.017-0.032 on CPU) and the int4
#: control's (0.37-0.67 on CPU). A cell's own limit is set at its own size
#: on the chip, where the gaps come out at other scales.
LIMIT = 0.1

#: peaks for a CPU run, so that readers have a row; never reported
CPU_PEAKS = {"bf16_flops": 1e12, "int8_ops": 2e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e9}


#: the configuration file of a DeepSeek-V2-Lite-shaped model small enough
#: for the CPU: one leading dense layer and two MoE layers, width 128, 8
#: routed experts (top 2) and 2 shared of width 64, latent rank 32
MOE_CONF = {
    "name": "deepseek-tiny", "registry": "deepseek-v2-lite-16b",
    "num_hidden_layers": 3, "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "intermediate_size": 256, "vocab_size": 512,
    "hidden_act": "silu", "norm": "rmsnorm", "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "first_k_dense_replace": 1, "n_routed_experts": 8,
    "num_experts_per_tok": 2, "moe_intermediate_size": 64,
    "n_shared_experts": 2, "kv_lora_rank": 32, "qk_nope_head_dim": 32,
    "qk_rope_head_dim": 16, "v_head_dim": 32, "weight_seed": 0,
}


def spec(name: str, limit: float = LIMIT, width: int = 128,
         vocab: int = 512) -> cell.Spec:
    full = cell.load_spec(name)
    conf = dict(full.conf, num_hidden_layers=2, hidden_size=width,
                num_attention_heads=4, num_key_value_heads=2,
                head_dim=width // 4, intermediate_size=2 * width,
                vocab_size=vocab, calibration={"batch": 1, "tokens": 64})
    mix = dict(full.mix, requests=64, clients=4,
               prompt={"dist": "uniform", "min": 40, "max": 120},
               output={"dist": "uniform", "min": 8, "max": 24},
               first_output={"dist": "uniform", "min": 1, "max": 24},
               warmup={"requests": 2, "prompt": 70, "output": 3})
    settings = dict(slots=4, page_size=16, pages=64, chunk=64, max_seq=256,
                    check={"requests": 2, "limit": {"logit_gap_mean": limit}})
    return cell.Spec(full.name, conf, mix, settings, full.end_to_end,
                     full.per_layer, 1)
