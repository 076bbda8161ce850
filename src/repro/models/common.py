"""Shared model machinery: config, quantization context, primitive layers.

Pure functional JAX (no flax): params are nested dicts of arrays; every
matmul in the network routes through `dense()`, which is where SPARQ plugs
in (off for bf16 training, calibrate to collect per-site activation stats,
quantized for the PTQ serving path).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.calibration import CalibBank
from repro.core.quantizer import QScale, quantize, weight_scale
from repro.core.sparq import SparqConfig
from repro.kernels.ops import quantized_matmul


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """One config per assigned architecture (src/repro/configs/)."""
    name: str
    family: str                  # dense | moe | rwkv6 | rglru | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    mlp_type: str = "swiglu"     # swiglu | gelu | geglu
    norm_type: str = "rmsnorm"   # rmsnorm | layernorm
    rope_theta: float = 10000.0
    use_rope: bool = True
    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    first_dense_layers: int = 0  # deepseek: first k layers use dense FFN
    capacity_factor: float = 1.25
    # --- MLA (deepseek) ---
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # --- RWKV6 ---
    head_size: int = 64
    decay_lora: int = 64
    # --- RG-LRU hybrid (recurrentgemma) ---
    block_pattern: tuple[str, ...] = ()   # e.g. ("rec", "rec", "attn")
    lru_width: int = 0
    local_window: int = 2048
    conv_width: int = 4
    # --- enc-dec (whisper) ---
    n_enc_layers: int = 0
    # --- modality frontend stubs (assignment: precomputed embeddings) ---
    frontend: str = "none"       # none | vision | audio
    frontend_len: int = 0
    # --- numerics / execution ---
    dtype: Any = jnp.bfloat16
    norm_eps: float = 1e-5
    tie_embeddings: bool = True
    logit_chunk: int = 0         # 0 = unchunked loss
    attn_chunk: int = 1024       # flash-style KV chunk in train/prefill
    mixer_impl: str = "chunked"  # rwkv/rglru sequence mixer: scan | chunked
    mixer_chunk: int = 16        # keeps chunked-WKV decay factors in f32
    train_microbatches: int = 1  # gradient accumulation (activation memory)
    param_dtype: Any = jnp.float32   # bf16 for >100B (f32 opt states)
    tensor_parallel: bool = True     # False: pure ZeRO-DP over all axes
                                     # (right choice for <~5B models)
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def is_encdec(self) -> bool:
        return self.family == "encdec"

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass
class QuantCtx:
    """How matmuls execute. `scales[site]` is a scalar per quantization site
    (or a (L,) stacked array outside scan bodies; the scan slices it)."""
    mode: str = "off"                     # off | calibrate | quantized
    cfg: Optional[SparqConfig] = None
    scales: Optional[Dict[str, Any]] = None
    collect: Optional[CalibBank] = None
    impl: str = "reference"               # reference | pallas
    skip_sites: tuple[str, ...] = ()      # paper: first layer left intact
    site_prefix: str = ""                 # per-layer prefix (calibration)
    stc: bool = False                     # Sparse-TC path (2:4-pruned w)
    mesh: Optional[Any] = None            # tensor-parallel jax Mesh

    @staticmethod
    def off() -> "QuantCtx":
        return QuantCtx(mode="off")


def dense(w, x: jnp.ndarray, site: str,
          ctx: Optional[QuantCtx] = None) -> jnp.ndarray:
    """x [..., d_in] @ w [d_in, d_out] through the quantization hook.
    `w` is either a float array or a pre-quantized {"q": int8, "s": f32}
    leaf (models.quantize.quantize_params, the serving deployment)."""
    from repro.models.quantize import as_weight, is_qweight
    if ctx is None or ctx.mode == "off" or site in (ctx.skip_sites or ()):
        return jnp.matmul(x, as_weight(w, x.dtype))
    if ctx.mode == "calibrate":
        if ctx.collect is not None:
            ctx.collect.observe(ctx.site_prefix + site, x)
        return jnp.matmul(x, as_weight(w, x.dtype))
    if ctx.mode == "quantized":
        cfg = ctx.cfg or SparqConfig.a8w8()
        scale = None
        if ctx.scales:
            key = ctx.site_prefix + site
            scale = ctx.scales.get(key, ctx.scales.get(site))
        if scale is None:
            scale = jnp.max(jnp.abs(x))  # dynamic per-tensor fallback
        qmax = cfg.max_val
        act_qs = QScale(scale=jnp.asarray(scale, jnp.float32) / qmax,
                        bits=cfg.act_bits, signed=cfg.signed)
        if ctx.stc:
            from repro.core.sparq import sparq_dot_stc
            return sparq_dot_stc(x, as_weight(w, jnp.float32),
                                 act_qs, cfg).astype(x.dtype)
        if is_qweight(w):
            w_codes, chan_scale = w["q"], w["s"]
        else:
            w_qs = weight_scale(w, cfg.weight_bits)
            w_codes = quantize(w, w_qs).astype(jnp.int8)
            chan_scale = w_qs.scale
        out = quantized_matmul(x, w_codes, act_qs, chan_scale, cfg,
                               impl=ctx.impl, mesh=ctx.mesh)
        return out.astype(x.dtype)
    raise ValueError(ctx.mode)


# ----------------------------------------------------------------------
# primitive layers
# ----------------------------------------------------------------------

def _sum_last(x: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last axis, keepdims, in one fixed order: halves added
    elementwise while the width is even. A reduce lets XLA choose its
    order per fusion, so the same norm compiled into two programs (the
    Pallas and the reference serving path, TP=4 and TP=1) can differ by
    an ulp, which the 8-bit activation quantizer turns into a code."""
    while x.shape[-1] % 2 == 0 and x.shape[-1] > 1:
        half = x.shape[-1] // 2
        x = x[..., :half] + x[..., half:]
    return jnp.sum(x, -1, keepdims=True)


def norm(params: Dict, x: jnp.ndarray, kind: str, eps: float) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        ms = _sum_last(xf * xf) / xf.shape[-1]
        xf = xf * jax.lax.rsqrt(ms + eps)
        return (xf * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    xf = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (xf * params["scale"].astype(jnp.float32)
            + params["bias"].astype(jnp.float32)).astype(x.dtype)


def norm_init(d: int, kind: str) -> Dict:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32),
            "bias": jnp.zeros((d,), jnp.float32)}


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         dims: Optional[int] = None) -> jnp.ndarray:
    """Rotary embedding over the last `dims` features. x: [B, T, H, hd]."""
    hd = dims or x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [B, T, half]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:hd]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    if hd < x.shape[-1]:
        rot = jnp.concatenate([rot, x[..., hd:]], -1)
    return rot.astype(x.dtype)


def sinusoidal_embed(T: int, d: int) -> jnp.ndarray:
    pos = jnp.arange(T, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / (10000.0 ** (2 * dim / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def init_dense(key, d_in: int, d_out: int, scale: float = 1.0,
               dtype=jnp.float32) -> jnp.ndarray:
    std = scale / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2, 2, (d_in, d_out)) *
            std).astype(dtype)


def embed_tokens(emb: jnp.ndarray, tokens: jnp.ndarray,
                 dtype) -> jnp.ndarray:
    return jnp.take(emb, tokens, axis=0).astype(dtype)


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray,
                       ignore: int = -1) -> jnp.ndarray:
    """Mean CE over non-ignored positions. logits [..., V], labels [...]."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = (labels != ignore).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def chunked_lm_loss(emb_out: jnp.ndarray, x: jnp.ndarray,
                    labels: jnp.ndarray, chunk: int) -> jnp.ndarray:
    """CE loss without materializing [T, vocab] logits: scan over sequence
    chunks, projecting to the vocab one chunk at a time (DESIGN.md §5)."""
    from repro.distributed.sharding import constrain
    x = constrain(x)
    B, T, D = x.shape
    if chunk <= 0 or T % chunk != 0 or T == chunk:
        logits = jnp.matmul(x, emb_out.astype(x.dtype))
        return cross_entropy_loss(logits, labels)
    n = T // chunk
    xc = x.reshape(B, n, chunk, D).swapaxes(0, 1)        # [n, B, c, D]
    lc = labels.reshape(B, n, chunk).swapaxes(0, 1)

    def body(carry, inp):
        xs, ls = inp
        logits = jnp.matmul(xs, emb_out.astype(xs.dtype)).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(ls, 0)[..., None], axis=-1)[..., 0]
        mask = (ls != -1).astype(jnp.float32)
        s, c = carry
        return (s + jnp.sum((lse - gold) * mask), c + jnp.sum(mask)), None

    (s, c), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (xc, lc))
    return s / jnp.maximum(c, 1.0)
