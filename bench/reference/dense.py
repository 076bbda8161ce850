"""Plain reference of a dense decoder: the published block, in float32.

Token embedding (times sqrt(hidden_size), as the program does), then per
layer: pre-norm (layernorm or RMSNorm), rotary attention with grouped
KV heads (query head h reads KV head h // (H / KV)), residual; pre-norm,
MLP (GELU-tanh, or SwiGLU), residual; a final norm and the head (the
embedding transposed when tied). Every matmul in float32 at `highest`
precision, over the bf16 weights `bench.weights` drew for the program.
No cache, no kernels, no batching of different requests into one
sequence: each sequence is one row, padded at its end.

It imports nothing of the program. Layer by layer, it draws that layer's
weights, so no more than one layer's weights are on the device at once.

`control=True` runs a second stream beside it, the step below the
program's int8 codes at the program's own granularity: every quantized
matmul's weights rounded to symmetric int4 per output channel (the
program: int8 per channel), its activations to int4 with one scale per
sequence and tensor (the program: 8-bit codes trimmed by SPARQ to 4-bit
windows, one static scale per site), and each layer's K and V to int4
with one scale per sequence (the program: its §5.1 pages, one scale per
sequence and site). The head stays in float, as the program keeps it.
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _int4(x, scale):
    """Symmetric int4 rounding with `scale` = max|x| / 7 (broadcast)."""
    s = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _per_channel(w):
    return _int4(w, jnp.max(jnp.abs(w), axis=0, keepdims=True) / 7.0)


def _per_sequence(x, mask):
    """One scale per sequence (row of the batch) over its valid positions;
    x [n, T, ...], mask [n, T]."""
    m = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
    amax = jnp.max(jnp.abs(jnp.where(m, x, 0.0)),
                   axis=tuple(range(1, x.ndim)), keepdims=True)
    return _int4(x, amax / 7.0)


def _mm(x, w, mask):
    """x @ w in float32; with a `mask` (the int4 stream), both operands
    rounded first."""
    w = w.astype(jnp.float32)
    if mask is not None:
        x, w = _per_sequence(x, mask), _per_channel(w)
    return jnp.einsum("...k,kn->...n", x, w, precision=HIGHEST)


def _norm(x, kind: str, eps: float):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta: float):
    """x [n, T, heads, hd], rotating the two halves of each head."""
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal attention, query blocks of Q_BLOCK rows. q [n,T,H,hd],
    k/v [n,T,KV,hd]."""
    n, T, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qb = q.reshape(n, T // Q_BLOCK, Q_BLOCK, KV, G, hd)
    kpos = jnp.arange(T)

    def block(i):
        s = jnp.einsum("nqkgh,nskh->nkgqs", qb[:, i], k,
                       precision=HIGHEST) * hd ** -0.5
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nkgqs,nskh->nqkgh", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(T // Q_BLOCK))  # [b,n,Q,KV,G,hd]
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(n, T, H * hd)


def _layer(x, w, cfg, mask):
    """One block; `mask` [n, T] of valid positions for the int4 stream,
    None for the float one."""
    kind, eps = cfg["norm"], cfg["eps"]
    H, KV, hd = cfg["heads"], cfg["kv_heads"], cfg["head_dim"]
    n, T, _ = x.shape
    h = _norm(x, kind, eps)
    q = _rope(_mm(h, w["wq"], mask).reshape(n, T, H, hd), cfg["theta"])
    k = _rope(_mm(h, w["wk"], mask).reshape(n, T, KV, hd), cfg["theta"])
    v = _mm(h, w["wv"], mask).reshape(n, T, KV, hd)
    if mask is not None:
        k, v = _per_sequence(k, mask), _per_sequence(v, mask)
    x = x + _mm(_attention(q, k, v), w["wo"], mask)
    h = _norm(x, kind, eps)
    if cfg["mlp"] == "swiglu":
        a = jax.nn.silu(_mm(h, w["w_gate"], mask)) * _mm(h, w["w_up"], mask)
    else:
        a = jax.nn.gelu(_mm(h, w["w_up"], mask), approximate=True)
    return x + _mm(a, w["w_down"], mask)


def sizes(conf: dict) -> dict:
    """The reference's view of a configuration file."""
    act = conf["hidden_act"]
    return {"layers": conf["num_hidden_layers"], "d": conf["hidden_size"],
            "heads": conf["num_attention_heads"],
            "kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["head_dim"], "ff": conf["intermediate_size"],
            "vocab": conf["vocab_size"], "theta": float(conf["rope_theta"]),
            "norm": conf["norm"],
            "eps": float(conf.get("rms_norm_eps",
                                  conf.get("norm_epsilon", 1e-5))),
            "mlp": "swiglu" if act == "silu" else "gelu",
            "tied": bool(conf["tie_word_embeddings"])}


def _matrices(cfg) -> List[Tuple[str, Tuple[int, int]]]:
    d, ff = cfg["d"], cfg["ff"]
    q, kv = cfg["heads"] * cfg["head_dim"], cfg["kv_heads"] * cfg["head_dim"]
    m = [("attn/wq", (d, q)), ("attn/wk", (d, kv)), ("attn/wv", (d, kv)),
         ("attn/wo", (q, d)), ("ffn/w_up", (d, ff)), ("ffn/w_down", (ff, d))]
    if cfg["mlp"] == "swiglu":
        m.append(("ffn/w_gate", (d, ff)))
    return m


def gaps(conf: dict, seed: int, seqs: List[np.ndarray], n_prompt: List[int],
         t_pad: int, control: bool = False) -> dict:
    """Per served token, how far the reference's logit of that token lies
    below the reference's best logit at its position.

    `seqs[i]` is prompt + served tokens; the first `n_prompt[i]` are the
    prompt. Served token j is predicted at position n_prompt[i] - 1 + j.
    With `control`, also the gap of the token that the int4 stream puts
    first at each of those positions. Returns {"served": [arrays],
    "control": [arrays] or None}."""
    cfg = sizes(conf)
    n = len(seqs)
    t_pad = -(-t_pad // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((n, t_pad), np.int32)
    valid = np.zeros((n, t_pad), bool)
    for i, s in enumerate(seqs):
        assert len(s) <= t_pad, (len(s), t_pad)
        toks[i, :len(s)] = s
        valid[i, :len(s)] = True
    valid = jnp.asarray(valid)
    draw = weights.layer_drawer(conf["weight_seed"])
    table = weights.table_drawer(conf["weight_seed"])
    emb = table("embed", (cfg["vocab"], cfg["d"]))
    with jax.default_matmul_precision("highest"):
        x0 = _embed(emb, jnp.asarray(toks), cfg["d"])
        xs = [x0, x0] if control else [x0]
        for layer in range(cfg["layers"]):
            w = {p.split("/")[1]: draw(f"blocks/0/{p}", layer, shp)
                 for p, shp in _matrices(cfg)}
            xs = [_LAYER(x, w, valid if i else None, _items(cfg))
                  for i, x in enumerate(xs)]
            del w
        head = emb.T if cfg["tied"] else table("lm_head",
                                               (cfg["d"], cfg["vocab"]))
        tgt = np.zeros((n, t_pad), np.int32)
        for i, s in enumerate(seqs):
            tgt[i, :len(s) - 1] = s[1:]
        served, ctl = _READOUT(xs[0], xs[1] if control else None, head,
                               jnp.asarray(tgt), _items(cfg))
    served, ctl = jax.device_get((served, ctl))
    out = {"served": [], "control": [] if control else None}
    for i, s in enumerate(seqs):
        lo, hi = n_prompt[i] - 1, len(s) - 1
        out["served"].append(np.asarray(served[i, lo:hi]))
        if control:
            out["control"].append(np.asarray(ctl[i, lo:hi]))
    return out


@jax.jit
def _embed_impl(emb, toks, scale):
    return jnp.take(emb, toks, axis=0).astype(jnp.float32) * scale


def _embed(emb, toks, d):
    return _embed_impl(emb, toks, jnp.float32(d ** 0.5))


def _items(cfg: dict) -> tuple:
    return tuple(sorted(cfg.items()))


def _readout_fn(x_ref, x_ctl, head, tgt, cfg_items):
    """Gaps at every position, T_BLOCK rows at a time: served gap =
    max(ref) - ref[target]; control gap = max(ref) - ref[argmax(ctl)]."""
    cfg = dict(cfg_items)
    n, T, d = x_ref.shape
    hf = head.astype(jnp.float32)
    h_ref = _norm(x_ref, cfg["norm"], cfg["eps"])
    h_ctl = None if x_ctl is None else _norm(x_ctl, cfg["norm"], cfg["eps"])

    def block(i):
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, i * Q_BLOCK,
                                                    Q_BLOCK, axis=1)
        lr = jnp.einsum("ntd,dv->ntv", sl(h_ref), hf, precision=HIGHEST)
        best = jnp.max(lr, -1)
        t = sl(tgt)
        g = best - jnp.take_along_axis(lr, t[..., None], -1)[..., 0]
        if h_ctl is None:
            return g, jnp.zeros_like(g)
        lc = jnp.einsum("ntd,dv->ntv", sl(h_ctl), hf, precision=HIGHEST)
        c = jnp.argmax(lc, -1)
        gc = best - jnp.take_along_axis(lr, c[..., None], -1)[..., 0]
        return g, gc

    g, gc = jax.lax.map(block, jnp.arange(T // Q_BLOCK))
    fix = lambda a: a.transpose(1, 0, 2).reshape(n, T)
    return fix(g), (None if x_ctl is None else fix(gc))


_LAYER = jax.jit(lambda x, w, mask, ci: _layer(x, w, dict(ci), mask),
                 static_argnums=(3,))
_READOUT = jax.jit(_readout_fn, static_argnums=(4,))
