"""Shared neural-net layers: norms, RoPE/M-RoPE/YaRN, GQA + MLA attention
(train, prefill and single-token decode paths), SwiGLU MLP, dropless MoE.

Param convention: every parameter is created as ``Param(value, axes)`` where
``axes`` is a tuple of *logical* axis names (see dist/sharding.py). The model
api splits the tree into (values, axes) so the launcher can derive
NamedShardings without a parallel spec tree drifting out of sync.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain, per_batch_shard


# ---------------------------------------------------------------------------
# Param container
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Param:
    value: jnp.ndarray
    axes: Tuple[Optional[str], ...]


def is_param(x) -> bool:
    return isinstance(x, Param)


def split_params(tree):
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=is_param)
    axes = jax.tree.map(lambda p: p.axes, tree, is_leaf=is_param)
    return values, axes


def _dense_init(key, shape, axes, scale=None, dtype=jnp.float32) -> Param:
    fan_in = shape[0] if len(shape) > 1 else shape[0]
    if scale is None:
        scale = 1.0 / math.sqrt(fan_in)
    v = jax.random.normal(key, shape, dtype) * scale
    return Param(v, axes)


def _zeros(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.zeros(shape, dtype), axes)


def _ones(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.ones(shape, dtype), axes)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_rmsnorm(d: int) -> Dict[str, Param]:
    return {"scale": _ones((d,), ("embed",))}


def rmsnorm(params, x, eps: float = 1e-5, use_kernel: bool = False):
    scale = params["scale"]
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.rmsnorm(x, scale, eps=eps)
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def head_rmsnorm(scale, x, eps: float = 1e-5):
    """qk-norm: rmsnorm over the head_dim of (B,S,H,hd)."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _quant_int8(x):
    """Per-(…, last-dim) symmetric int8 quantization: returns (q, scale)."""
    x32 = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x32), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard / partial / M-RoPE)
# ---------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, 0.1 m ln s + 1 (1 for s <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_ramp(rot_dim: int, theta: float, sc) -> jnp.ndarray:
    """Share (0..1) of each frequency that is interpolated: 0 below the
    correction dim of `beta_fast` rotations over the original context,
    1 above that of `beta_slow`, linear between (DeepSeek-V2's YaRN)."""
    def dim_of(rotations):
        return (rot_dim * math.log(sc.original_max_position_embeddings
                                   / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dim_of(sc.beta_fast)), 0)
    high = min(math.ceil(dim_of(sc.beta_slow)), rot_dim - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(rot_dim // 2, dtype=jnp.float32)
    return jnp.clip((i - low) / (high - low), 0.0, 1.0)


def rope_freqs(rot_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    inv = 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32) / rot_dim))
    if scaling is None:
        return inv
    ramp = _yarn_ramp(rot_dim, theta, scaling)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def softmax_scale(qk_dim: int, scaling=None) -> float:
    """1/sqrt(qk_dim), times YaRN's mscale(factor, mscale_all_dim)^2."""
    scale = 1.0 / math.sqrt(qk_dim)
    if scaling is not None and scaling.mscale_all_dim:
        scale *= yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2
    return scale


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               rot_frac: float = 1.0,
               mrope_sections: Tuple[int, ...] = (),
               scaling=None) -> jnp.ndarray:
    """x: (B,S,H,hd). positions: (B,S) or (3,B,S) for M-RoPE. `scaling`:
    a `RopeScaling` (YaRN) or None."""
    hd = x.shape[-1]
    rot_dim = int(hd * rot_frac)
    if rot_dim == 0:
        return x
    rot_dim -= rot_dim % 2
    inv = rope_freqs(rot_dim, theta, scaling)  # (rot_dim/2,)
    if mrope_sections:
        assert positions.ndim == 3, "M-RoPE needs (3,B,S) positions"
        secs = mrope_sections
        assert sum(secs) == rot_dim // 2, (secs, rot_dim)
        parts = []
        off = 0
        for i, s in enumerate(secs):
            ang = positions[i][..., None].astype(jnp.float32) * inv[off:off + s]
            parts.append(ang)
            off += s
        angles = jnp.concatenate(parts, axis=-1)  # (B,S,rot_dim/2)
    else:
        angles = positions[..., None].astype(jnp.float32) * inv  # (B,S,rot_dim/2)
    cos = jnp.cos(angles)[:, :, None, :]  # (B,S,1,rot_dim/2)
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    x1, x2 = xr[..., : rot_dim // 2], xr[..., rot_dim // 2:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1.astype(x.dtype), out2.astype(x.dtype), xp], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA). Chunked online-softmax full attention keeps peak memory
# O(S * chunk) instead of O(S^2) — same math as kernels/ref.py oracle.
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig) -> Dict[str, Param]:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], (d, H, hd), ("embed", "heads", None)),
        "wk": _dense_init(ks[1], (d, KV, hd), ("embed", "kv_heads", None)),
        "wv": _dense_init(ks[2], (d, KV, hd), ("embed", "kv_heads", None)),
        "wo": _dense_init(ks[3], (H, hd, d), ("heads", None, "embed"),
                          scale=1.0 / math.sqrt(H * hd)),
    }
    if cfg.qk_norm:
        p["q_norm"] = _ones((hd,), (None,))
        p["k_norm"] = _ones((hd,), (None,))
    return p


def key_extents(Sq: int, Sk: int, causal: bool, q_offset: int,
                chunk: int) -> Tuple[int, ...]:
    """The number of keys each query chunk of `_chunked_attn` scores.

    Sq <= chunk is one chunk against all Sk keys. Otherwise there are
    Sq/chunk chunks; under the causal mask chunk i sees only the keys up
    to its last query, q_offset + (i+1)*chunk, so the key blocks the mask
    hides whole are left out (36 of 64 at Sq = Sk = 8 x chunk).
    """
    if Sq <= chunk:
        return (Sk,)
    n = Sq // chunk
    if not causal:
        return (Sk,) * n
    return tuple(min(Sk, q_offset + (i + 1) * chunk) for i in range(n))


def _chunked_attn(q, k, v, causal: bool, q_offset, scale=None,
                  chunk: int = 1024):
    """q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd) -> (B,Sq,H,hd). GQA by head broadcast.
    `scale`: the softmax scale (default 1/sqrt(hd)).

    Query chunk i takes a full softmax against the first
    `key_extents(...)[i]` keys, a static length per chunk: under the causal
    mask no score is computed for a key block the mask hides whole, and
    the mask only bites in the chunk's last key block. The chunks are
    unrolled, each under `jax.checkpoint`, so the backward pass recomputes
    one chunk's scores (B*chunk*extent per head) at a time, and the
    compiler schedules one chunk's scores at a time in both passes: the
    8k DeepSeek-V2 step's compiled peak is within 6 MB of a scan over the
    chunks. Forcing the order with optimization barriers (`prevent_cse`'s
    own, or a chain between chunks) raised that peak by 1.6 GB or more.
    """
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    vd = v.shape[-1]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    if Sq <= chunk:
        return _attn_block(qg, k, v, causal, q_offset, 0, scale
                           ).reshape(B, Sq, H, vd)
    assert Sq % chunk == 0, (Sq, chunk)

    @functools.partial(jax.checkpoint, static_argnums=(3, 4),
                       prevent_cse=False)
    def block(qg, k, v, start, extent):
        return _attn_block(qg[:, start:start + chunk], k[:, :extent],
                           v[:, :extent], causal, q_offset, start, scale)

    outs = [block(qg, k, v, i * chunk, extent) for i, extent in
            enumerate(key_extents(Sq, Sk, causal, q_offset, chunk))]
    return jnp.stack(outs, axis=1).reshape(B, Sq, H, vd)


def _attn_block(qg, k, v, causal, q_offset, block_start, scale):
    """qg:(B,sq,KV,G,hd) against full k,v:(B,Sk,KV,hd)."""
    B, sq, KV, G, hd = qg.shape
    Sk = k.shape[1]
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        qpos = q_offset + block_start + jnp.arange(sq)
        kpos = jnp.arange(Sk)
        mask = kpos[None, :] <= qpos[:, None]  # (sq,Sk)
        logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return out.astype(qg.dtype)


def _cache_store(buf, val, index):
    """Write a decode-step slice into ``buf`` at position ``index`` (axis 1).

    ``index`` is either a scalar — lockstep decode, every row at the same
    depth (the original `dynamic_update_slice` path, bit-identical) — or a
    (B,) vector of per-row positions for continuous batching, where each
    slot sits at its own depth. The vector path requires S == 1 steps.
    """
    val = val.astype(buf.dtype)
    if jnp.ndim(index) == 0:
        return lax.dynamic_update_slice(
            buf, val, (0, index) + (0,) * (buf.ndim - 2))
    return buf.at[jnp.arange(buf.shape[0]), index].set(val[:, 0])


def _cache_valid(index, S, Sk, n_between):
    """Mask of attendable key positions: kpos <= index + S - 1, shaped with
    ``n_between`` singleton dims between the (optional) batch dim and Sk so
    it broadcasts against the decode logits."""
    kpos = jnp.arange(Sk).reshape((1,) * (n_between + 1) + (Sk,))
    last = index + S - 1
    if jnp.ndim(index) == 0:
        return kpos <= last
    return kpos <= last.reshape((-1,) + (1,) * (n_between + 1))


def attention(params, cfg: ModelConfig, x, positions,
              cache: Optional[Dict[str, jnp.ndarray]] = None,
              cache_index=None):
    """Full attention. If ``cache`` given: decode path (x is (B,1,d)); returns
    (out, new_cache). Otherwise train/prefill; returns (out, None)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    if cfg.qk_norm:
        q = head_rmsnorm(params["q_norm"], q, cfg.norm_eps)
        k = head_rmsnorm(params["k_norm"], k, cfg.norm_eps)
    if cfg.partial_rotary > 0:
        q = apply_rope(q, positions, cfg.rope_theta, cfg.partial_rotary,
                       cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.partial_rotary,
                       cfg.mrope_sections)
    q = constrain(q, "batch", "seq", "heads", None)
    k = constrain(k, "batch", "seq", "kv_heads", None)

    if cache is not None:
        if cfg.kv_quant:
            # int8 KV cache: per-(token, head) scales — halves the decode
            # memory roofline (the dominant term for every decode cell)
            kq, ks_ = _quant_int8(k)
            vq, vs_ = _quant_int8(v)
            ck = _cache_store(cache["k"], kq, cache_index)
            cv = _cache_store(cache["v"], vq, cache_index)
            cks = _cache_store(cache["k_scale"], ks_, cache_index)
            cvs = _cache_store(cache["v_scale"], vs_, cache_index)
            new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
            ck = ck.astype(jnp.bfloat16) * cks[..., None].astype(jnp.bfloat16)
            cv = cv.astype(jnp.bfloat16) * cvs[..., None].astype(jnp.bfloat16)
        else:
            ck = _cache_store(cache["k"], k, cache_index)
            cv = _cache_store(cache["v"], v, cache_index)
            new_cache = {"k": ck, "v": cv}
        ck = constrain(ck, "batch", "kv_seq", "kv_heads", None)
        cv = constrain(cv, "batch", "kv_seq", "kv_heads", None)
        Sk = ck.shape[1]
        valid = _cache_valid(cache_index, S, Sk, 3)
        KV = ck.shape[2]
        G = cfg.n_heads // KV
        qg = q.reshape(B, S, KV, G, cfg.head_dim)
        logits = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                            ck.astype(jnp.float32)) / math.sqrt(cfg.head_dim)
        logits = jnp.where(valid, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bkgqs,bskh->bqkgh", w, cv.astype(jnp.float32))
        out = out.reshape(B, S, cfg.n_heads, cfg.head_dim).astype(x.dtype)
    else:
        new_cache = None
        if cfg.use_pallas:
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=cfg.causal)
        else:
            out = _chunked_attn(q, k, v, cfg.causal, 0)
    out = constrain(out, "batch", "seq", "heads", None)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return constrain(y, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2): latent-compressed KV. Train path materializes
# per-head K/V; decode path uses the absorbed formulation against the compact
# (c_kv, k_rope) cache — the technique's memory win.
# ---------------------------------------------------------------------------
def init_mla(key, cfg: ModelConfig) -> Dict[str, Param]:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": _dense_init(ks[0], (d, H, qk_head), ("embed", "heads", None)),
        "wdkv": _dense_init(ks[1], (d, m.kv_lora_rank), ("embed", "qk_lora")),
        "wkrope": _dense_init(ks[2], (d, m.qk_rope_head_dim), ("embed", None)),
        "wuk": _dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim),
                           ("qk_lora", "heads", None)),
        "wuv": _dense_init(ks[4], (m.kv_lora_rank, H, m.v_head_dim),
                           ("qk_lora", "heads", None)),
        "wo": _dense_init(ks[5], (H, m.v_head_dim, d), ("heads", None, "embed"),
                          scale=1.0 / math.sqrt(H * m.v_head_dim)),
        "kv_norm": _ones((m.kv_lora_rank,), (None,)),
    }


@jax.named_scope("mla.attention")
def mla_attention(params, cfg: ModelConfig, x, positions,
                  cache: Optional[Dict[str, jnp.ndarray]] = None,
                  cache_index=None):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    sc = cfg.rope_scaling
    scale = softmax_scale(nope + rope_d, sc)

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, scaling=sc)
    c_kv = x @ params["wdkv"].astype(x.dtype)                       # (B,S,r)
    c_kv = rmsnorm({"scale": params["kv_norm"]}, c_kv, cfg.norm_eps)
    k_rope = (x @ params["wkrope"].astype(x.dtype))[:, :, None, :]  # (B,S,1,rd)
    k_rope = apply_rope(k_rope, positions, cfg.rope_theta,
                        scaling=sc)[:, :, 0]                            # (B,S,rd)

    if cache is not None:
        # absorbed decode: q_lat = q_nope @ W_uk  -> score against c_kv cache
        cc = _cache_store(cache["c_kv"], c_kv, cache_index)
        cr = _cache_store(cache["k_rope"], k_rope, cache_index)
        cc = constrain(cc, "batch", "kv_seq", "qk_lora")
        cr = constrain(cr, "batch", "kv_seq", None)
        new_cache = {"c_kv": cc, "k_rope": cr}
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(jnp.float32),
                           params["wuk"].astype(jnp.float32))
        logits = (jnp.einsum("bshr,btr->bhst", q_lat, cc.astype(jnp.float32))
                  + jnp.einsum("bshr,btr->bhst", q_rope.astype(jnp.float32),
                               cr.astype(jnp.float32))) * scale
        Sk = cc.shape[1]
        valid = _cache_valid(cache_index, S, Sk, 2)
        logits = jnp.where(valid, logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", w, cc.astype(jnp.float32))
        out = jnp.einsum("bshr,rhv->bshv", o_lat,
                         params["wuv"].astype(jnp.float32)).astype(x.dtype)
    else:
        new_cache = None
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, params["wuk"].astype(x.dtype))
        v = jnp.einsum("bsr,rhv->bshv", c_kv, params["wuv"].astype(x.dtype))
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, rope_d))],
            axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        q_full = constrain(q_full, "batch", "seq", "heads", None)
        k_full = constrain(k_full, "batch", "seq", "heads", None)
        out = _chunked_attn(q_full, k_full, v, cfg.causal, 0, scale)
    out = constrain(out, "batch", "seq", "heads", None)
    y = jnp.einsum("bshv,hvd->bsd", out, params["wo"].astype(x.dtype))
    return constrain(y, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, d_ff: int, variant: str = "swiglu"
             ) -> Dict[str, Param]:
    ks = jax.random.split(key, 3)
    p = {
        "wi": _dense_init(ks[0], (d, d_ff), ("embed", "ff")),
        "wo": _dense_init(ks[2], (d_ff, d), ("ff", "embed")),
    }
    if variant == "swiglu":
        p["wg"] = _dense_init(ks[1], (d, d_ff), ("embed", "ff"))
    return p


def mlp(params, x):
    if "wg" in params:  # SwiGLU
        h = jax.nn.silu(x @ params["wg"].astype(x.dtype)) * (
            x @ params["wi"].astype(x.dtype))
    else:               # 2-matrix GELU (starcoder2-style)
        h = jax.nn.gelu(x @ params["wi"].astype(x.dtype))
    h = constrain(h, "batch", "seq", "ff")
    return constrain(h @ params["wo"].astype(x.dtype), "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# MoE: dropless routing over the experts this layer holds. The router scores
# all experts; the assignments that land on a held expert are sorted by
# expert and run through a grouped matmul (`lax.ragged_dot`), with no
# capacity and nothing dropped. The sort and the grouped matmul run on each
# shard of a data-sharded batch by itself, over its own tokens. A layer
# holding a share of the experts (one chip of an expert-parallel layer)
# gives that share's part of the result.
# ---------------------------------------------------------------------------
MOE_COUNTERS = ("moe_routed_held", "moe_max_load")


def init_moe(key, cfg: ModelConfig) -> Dict[str, Param]:
    mo = cfg.moe
    d, E, n, f = cfg.d_model, mo.n_experts, mo.held, mo.expert_d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense_init(ks[0], (d, E), ("embed", "experts"),
                              scale=0.02),
        "wi": _dense_init(ks[1], (n, d, f), ("experts", "embed", "ff")),
        "wg": _dense_init(ks[2], (n, d, f), ("experts", "embed", "ff")),
        "wo": _dense_init(ks[3], (n, f, d), ("experts", "ff", "embed")),
    }
    if mo.n_shared_experts:
        p["shared"] = init_mlp(ks[4], d, mo.n_shared_experts * f)
    return p


def _balance_loss(probs, top_e, mo, batch: int):
    """E * sum_e f_e * P_e, times the coefficient: f_e is expert e's share
    of the top-k assignments over E, P_e its mean router probability. Over
    each sequence and then averaged with `seq_aux` (DeepSeek-V2), else over
    all tokens at once."""
    E, k = mo.n_experts, mo.top_k
    groups = batch if mo.seq_aux else 1
    p = probs.reshape(groups, -1, E)
    e = top_e.reshape(groups, -1, k)
    ce = jnp.sum(jax.nn.one_hot(e, E, dtype=jnp.float32), axis=(1, 2)) / (
        e.shape[1] * k)
    return E * jnp.mean(jnp.sum(jnp.mean(p, axis=1) * ce, axis=-1)) * \
        mo.aux_loss_coef


@jax.checkpoint
def _held_experts(xf, tok, w, sizes, wg, wi, wo):
    """xf: (T,d) tokens; tok, w: (M,) each assignment's token and gate,
    sorted by held expert; sizes: (n,) assignments per held expert, the
    rows past their sum unused. -> (T,d) float32: each token's gated sum
    of its held experts' outputs. The buffer holds every assignment that
    can land here, so it is recomputed in the backward pass rather than
    kept: only the tokens and the routing are saved."""
    # the grouped matmuls leave the rows past the groups unwritten (on the
    # TPU they hold whatever the buffer held): select them away on the way
    # in and out, so that neither pass reads them
    used = (jnp.arange(tok.shape[0]) < jnp.sum(sizes))[:, None]
    with jax.named_scope("moe.dispatch"):
        xs = jnp.where(used, xf[tok], 0)
    with jax.named_scope("moe.experts"):
        h = jax.nn.silu(lax.ragged_dot(xs, wg.astype(xs.dtype), sizes)) * \
            lax.ragged_dot(xs, wi.astype(xs.dtype), sizes)
        h = h * w[:, None].astype(h.dtype)
        out = lax.ragged_dot(h, wo.astype(xs.dtype), sizes)
    with jax.named_scope("moe.combine"):
        return jnp.zeros(xf.shape, jnp.float32).at[tok].add(
            jnp.where(used, out.astype(jnp.float32), 0.0))


def _routed(x, top_e, top_w, wg, wi, wo, *, first: int):
    """One shard's tokens x: (B,S,d), their top-k experts and gates ->
    ((B,S,d) float32 held experts' part, (1,n) assignments per held
    expert)."""
    B, S, d = x.shape
    k, n = top_e.shape[-1], wg.shape[0]
    with jax.named_scope("moe.dispatch"):
        local = top_e.reshape(-1) - first
        held = (local >= 0) & (local < n)
        # held assignments sorted by expert; the others sort last, past
        # every group, where the grouped matmuls neither read nor write
        slot = jnp.where(held, local, n)
        order = jnp.argsort(slot, stable=True)[:B * S * min(k, n)]
        sizes = jnp.zeros((n + 1,), jnp.int32).at[slot].add(1)[:n]
        tok = order // k
        # zero past the held rows, the backward pass's too
        w = jnp.where(held[order], top_w.reshape(-1)[order], 0.0)
    y = _held_experts(x.reshape(B * S, d), tok, w, sizes, wg, wi, wo)
    return y.reshape(B, S, d), sizes[None]


def moe(params, cfg: ModelConfig, x):
    """x: (B,S,d) -> (y, aux_loss, counters). `y` is the held experts' part
    of the routed result plus the shared experts; `counters` gives the
    assignments computed here and the largest held expert's load."""
    mo = cfg.moe
    B = x.shape[0]

    with jax.named_scope("moe.route"):
        logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                            params["router"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        probs = jax.nn.softmax(logits, axis=-1)
        # on each shard: the partitioner gathers the batch for a top_k
        top_w, top_e = per_batch_shard(
            functools.partial(lax.top_k, k=mo.top_k), (probs,), ())
        if mo.norm_topk_prob:
            top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        aux = _balance_loss(probs, top_e, mo, B)

    y, sizes = per_batch_shard(
        functools.partial(_routed, first=mo.first_held), (x, top_e, top_w),
        (params["wg"], params["wi"], params["wo"]))
    y = y.astype(x.dtype)
    if mo.n_shared_experts:
        y = y + mlp(params["shared"], x)
    sizes = jnp.sum(sizes, axis=0)
    counters = {"moe_routed_held": jnp.sum(sizes),
                "moe_max_load": jnp.max(sizes)}
    return constrain(y, "batch", "seq", "embed"), aux, counters
