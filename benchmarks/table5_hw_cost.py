"""Table 5 analogue: hardware cost model of the SPARQ kernel on TPU.

The paper reports post-layout silicon area per PE; a TPU's MXU is fixed, so
the deployable analogue is the *kernel cost model*: HLO FLOPs and bytes of
the fused sparq_matmul vs a plain int8 matmul (same tiles), the VMEM
working set implied by the BlockSpecs, and the packed HBM bytes/value of
each configuration (the paper's §5.1 metadata-footprint discussion).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sparq import SparqConfig
from repro.kernels.ops import bytes_per_value
from repro.kernels.sparq_matmul import sparq_matmul_pallas, vmem_bytes


def kernel_cost(cfg: SparqConfig, m=256, k=1024, n=256,
                block=(128, 128, 512)):
    bm, bn, bk = block
    x = jax.ShapeDtypeStruct((m, k), jnp.float32)
    w = jax.ShapeDtypeStruct((k, n), jnp.int8)
    a = jax.ShapeDtypeStruct((), jnp.float32)
    c = jax.ShapeDtypeStruct((n,), jnp.float32)
    kw = dict(bits=cfg.bits, opts_shifts=cfg.shifts, rounding=cfg.rounding,
              vsparq=cfg.vsparq, signed=cfg.signed, max_val=cfg.max_val,
              enabled=cfg.enabled, bm=bm, bn=bn, bk=bk, interpret=True)
    lowered = jax.jit(
        lambda xx, ww, aa, cc: sparq_matmul_pallas(xx, ww, aa, cc, **kw)
    ).lower(x, w, a, c)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    return {
        "flops": float(cost.get("flops", -1)),
        "bytes": float(cost.get("bytes accessed", -1)),
        "vmem_bytes": vmem_bytes(bm, bn, bk, k, signed=cfg.signed,
                                 max_val=cfg.max_val),
        "packed_bits_per_act": round(bytes_per_value(cfg) * 8, 2),
    }


def table5_rows():
    rows = []
    configs = [
        ("8b8b_baseline", SparqConfig(enabled=False, signed=True)),
        ("7opt_2b", SparqConfig.opt7(signed=True)),
        ("6opt_3b", SparqConfig.opt6(signed=True)),
        ("5opt_4b", SparqConfig.opt5(signed=True)),
        ("3opt_4b", SparqConfig.opt3(signed=True)),
        ("2opt_4b", SparqConfig.opt2(signed=True)),
        ("5opt_noVS", SparqConfig.opt5(signed=True, vsparq=False)),
        ("3opt_noVS", SparqConfig.opt3(signed=True, vsparq=False)),
    ]
    base = None
    for name, cfg in configs:
        c = kernel_cost(cfg)
        if base is None:
            base = c
        rows.append((name, "hlo_flops_rel",
                     round(c["flops"] / max(base["flops"], 1), 3)))
        rows.append((name, "hlo_bytes_rel",
                     round(c["bytes"] / max(base["bytes"], 1), 3)))
        rows.append((name, "vmem_bytes", c["vmem_bytes"]))
        rows.append((name, "packed_bits_per_act", c["packed_bits_per_act"]))
    return rows
