"""Decoder-only transformer LM covering the dense / moe / vlm families.

Layers are scanned (stacked params, jax.lax.scan) so HLO size is O(1) in depth
— essential for the 62-compile dry-run sweep. Activation checkpointing policy
comes from cfg.remat.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.dist.sharding import constrain
from repro.models import layers as L


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _stack(trees):
    return jax.tree.map(lambda *xs: L.Param(
        jnp.stack([x.value for x in xs]), ("layers",) + xs[0].axes),
        *trees, is_leaf=L.is_param)


def init_layer(key, cfg: ModelConfig, dense_ffn: bool) -> Dict[str, Any]:
    k1, k2, k3 = jax.random.split(key, 3)
    p: Dict[str, Any] = {
        "ln1": L.init_rmsnorm(cfg.d_model),
        "ln2": L.init_rmsnorm(cfg.d_model),
    }
    if cfg.mla is not None:
        p["attn"] = L.init_mla(k1, cfg)
    else:
        p["attn"] = L.init_attention(k1, cfg)
    if cfg.moe is not None and not dense_ffn:
        p["moe"] = L.init_moe(k2, cfg)
    else:
        d_ff = cfg.dense_d_ff if (dense_ffn and cfg.dense_d_ff) else cfg.d_ff
        p["mlp"] = L.init_mlp(k2, cfg.d_model, d_ff, cfg.mlp_variant)
    return p


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.n_layers + 3)
    n_dense = cfg.first_k_dense
    dense_layers = [init_layer(keys[i], cfg, dense_ffn=True)
                    for i in range(n_dense)]
    scanned = [init_layer(keys[n_dense + i], cfg, dense_ffn=False)
               for i in range(cfg.n_layers - n_dense)]
    p: Dict[str, Any] = {
        "embed": L._dense_init(keys[-1], (cfg.vocab_size, cfg.d_model),
                               ("vocab", "embed"), scale=0.02),
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "layers": _stack(scanned),
    }
    if dense_layers:
        p["dense_layers"] = _stack(dense_layers)
    if not cfg.tie_embeddings:
        p["lm_head"] = L._dense_init(keys[-2], (cfg.d_model, cfg.vocab_size),
                                     ("embed", "vocab"))
    return p


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _layer_apply(lp, cfg: ModelConfig, x, positions, is_dense_ffn: bool,
                 cache=None, cache_index=None):
    """-> (x, aux loss, counters, new cache); counters are the MoE layer's
    (`L.MOE_COUNTERS`), {} for a dense FFN."""
    attn_fn = L.mla_attention if cfg.mla is not None else L.attention
    h, new_cache = attn_fn(lp["attn"], cfg, L.rmsnorm(lp["ln1"], x, cfg.norm_eps),
                           positions, cache, cache_index)
    x = x + h
    ffn_in = L.rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if "moe" in lp and not is_dense_ffn:
        y, aux, counters = L.moe(lp["moe"], cfg, ffn_in)
    else:
        y, aux, counters = (L.mlp(lp["mlp"], ffn_in),
                            jnp.zeros((), jnp.float32), {})
    return x + y, aux, counters, new_cache


def _remat(fn, cfg: ModelConfig):
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return fn


def scan_layers(body, x, stacked, cfg: ModelConfig):
    """lax.scan over stacked layer params, or an unrolled python loop when
    cfg.unroll_layers (dry-run probes: makes XLA cost_analysis see each layer)."""
    if not cfg.unroll_layers:
        return lax.scan(_remat(body, cfg), x, stacked)
    n = jax.tree.leaves(stacked)[0].shape[0]
    rematted = _remat(body, cfg)
    ys = []
    for i in range(n):
        sl = jax.tree.map(lambda v: v[i], stacked)
        x, y = rematted(x, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys = None
    return x, ys


def forward(params, cfg: ModelConfig, tokens, positions=None,
            input_embeds=None):
    """tokens: (B,S) int32 (or input_embeds (B,S,d) for stubbed frontends).
    positions: (B,S) or (3,B,S) for M-RoPE. Returns logits (B,S,V) and aux loss.
    """
    logits, aux, _ = forward_with_counters(params, cfg, tokens, positions,
                                           input_embeds)
    return logits, aux


def forward_with_counters(params, cfg: ModelConfig, tokens, positions=None,
                          input_embeds=None):
    """`forward`, and the MoE layers' counters: assignments computed here,
    summed over the layers, and the largest held expert's load in any
    layer ({} without expert layers)."""
    if input_embeds is not None:
        x = input_embeds.astype(cfg.dtype)
        B, S = x.shape[:2]
    else:
        B, S = tokens.shape
        x = params["embed"].astype(cfg.dtype)[tokens]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(positions[None], (3, B, S))
    x = constrain(x, "batch", "seq", "embed")

    aux_total = jnp.zeros((), jnp.float32)
    if "dense_layers" in params:
        def dense_body(x, lp):
            x, aux, _, _ = _layer_apply(lp, cfg, x, positions,
                                        is_dense_ffn=True)
            return x, aux
        x, auxs = scan_layers(dense_body, x, params["dense_layers"], cfg)
        aux_total = aux_total + jnp.sum(auxs)

    def body(x, lp):
        x, aux, counters, _ = _layer_apply(lp, cfg, x, positions,
                                           is_dense_ffn=False)
        return x, (aux, counters)

    x, (auxs, per_layer) = scan_layers(body, x, params["layers"], cfg)
    aux_total = aux_total + jnp.sum(auxs)
    counters = {}
    if per_layer:
        counters = {"moe_routed_held": jnp.sum(per_layer["moe_routed_held"]),
                    "moe_max_load": jnp.max(per_layer["moe_max_load"])}

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(cfg.dtype)
    return constrain(logits, "batch", "seq", "vocab"), aux_total, counters


# ---------------------------------------------------------------------------
# KV cache + decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    n_scan = cfg.n_layers - cfg.first_k_dense
    if cfg.mla is not None:
        m = cfg.mla
        mk = lambda n, *shape, axes: L.Param(  # noqa: E731
            jnp.zeros((n,) + shape, dtype), ("layers",) + axes)
        c: Dict[str, Any] = {"layers": {
            "c_kv": mk(n_scan, batch, max_len, m.kv_lora_rank,
                       axes=("batch", "kv_seq", "qk_lora")),
            "k_rope": mk(n_scan, batch, max_len, m.qk_rope_head_dim,
                         axes=("batch", "kv_seq", None)),
        }}
        if cfg.first_k_dense:
            c["dense_layers"] = {
                "c_kv": mk(cfg.first_k_dense, batch, max_len, m.kv_lora_rank,
                           axes=("batch", "kv_seq", "qk_lora")),
                "k_rope": mk(cfg.first_k_dense, batch, max_len,
                             m.qk_rope_head_dim, axes=("batch", "kv_seq", None)),
            }
        return c
    kv, hd = cfg.n_kv_heads, cfg.head_dim

    def mk(n):
        kv_dtype = jnp.int8 if cfg.kv_quant else dtype
        d = {
            "k": L.Param(jnp.zeros((n, batch, max_len, kv, hd), kv_dtype),
                         ("layers", "batch", "kv_seq", "kv_heads", None)),
            "v": L.Param(jnp.zeros((n, batch, max_len, kv, hd), kv_dtype),
                         ("layers", "batch", "kv_seq", "kv_heads", None)),
        }
        if cfg.kv_quant:
            d["k_scale"] = L.Param(
                jnp.zeros((n, batch, max_len, kv), jnp.float32),
                ("layers", "batch", "kv_seq", "kv_heads"))
            d["v_scale"] = L.Param(
                jnp.zeros((n, batch, max_len, kv), jnp.float32),
                ("layers", "batch", "kv_seq", "kv_heads"))
        return d

    c = {"layers": mk(n_scan)}
    if cfg.first_k_dense:
        c["dense_layers"] = mk(cfg.first_k_dense)
    return c


def decode_step(params, cfg: ModelConfig, cache, tokens, index):
    """One decode step. tokens: (B,) int32; index: scalar position, or a
    (B,) vector of per-row positions (continuous batching — each slot at
    its own depth). Returns (logits (B,V), new_cache)."""
    B = tokens.shape[0]
    x = params["embed"].astype(cfg.dtype)[tokens][:, None]  # (B,1,d)
    if jnp.ndim(index) == 0:
        pos = jnp.full((B, 1), index, jnp.int32)
    else:
        pos = index.astype(jnp.int32)[:, None]
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[None], (3, B, 1))
    x = constrain(x, "batch", None, "embed")

    def scan_group(x, group_params, group_cache, dense):
        def body(x, lp_and_cache):
            lp, lc = lp_and_cache
            x, _, _, new_c = _layer_apply(lp, cfg, x, pos, dense,
                                          cache=lc, cache_index=index)
            return x, new_c
        return scan_layers(body, x, (group_params, group_cache), cfg)

    new_cache: Dict[str, Any] = {}
    if "dense_layers" in params:
        x, nc = scan_group(x, params["dense_layers"], cache["dense_layers"], True)
        new_cache["dense_layers"] = nc
    x, nc = scan_group(x, params["layers"], cache["layers"], False)
    new_cache["layers"] = nc

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = (x @ head.astype(cfg.dtype))[:, 0]
    return constrain(logits, "batch", "vocab"), new_cache
