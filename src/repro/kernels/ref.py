"""Pure-jnp oracles for every Pallas kernel. Tests sweep shapes/dtypes and
assert_allclose kernel-vs-ref; the model code paths also use these refs when
kernels are disabled.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, causal: bool = True, scale=None):
    """q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd) -> (B,Sq,H,hd), fp32 softmax."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, k.shape[1]), bool), k.shape[1] - Sq)
        logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def event_select_ref(ev):
    """Oracle for the event-select kernel: per-row masked min + argmin over
    an (n, m) candidate-event matrix (inf = masked), ties broken by lowest
    column index. All-masked rows return (inf, 0) — NumPy argmin semantics,
    the contract both fleet engines share (docs/DESIGN.md §2)."""
    t = jnp.min(ev, axis=1)
    i = jnp.argmin(ev, axis=1).astype(jnp.int32)
    return t, i


def rmsnorm_ref(x, scale, eps: float = 1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def ssd_scan_ref(x, dt, A, B, C, chunk: int = 64):
    """Oracle for the Mamba2 SSD kernel — delegates to models.ssm.ssd."""
    from repro.models.ssm import ssd
    return ssd(x.astype(jnp.float32), dt.astype(jnp.float32), A,
               B.astype(jnp.float32), C.astype(jnp.float32),
               chunk=chunk).astype(x.dtype)
