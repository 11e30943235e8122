"""FlashAttention for TPU in Pallas: fwd + bwd kernels with explicit BlockSpec
VMEM tiling, causal + GQA. Grid iterates KV blocks in the minor-most dimension
so the online-softmax accumulators live in VMEM scratch across iterations
(the canonical TPU pattern — sequential grid, MXU-aligned 128x128 tiles).

Validated on CPU via interpret=True against ref.flash_attention_ref.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1  # block intersects causal tri

    @pl.when(jnp.asarray(run))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # (bq, hd)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev = m_ref[...]                           # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_safe)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "scale"))
def flash_attention_fwd(q, k, v, *, causal=True, scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        interpret=False):
    """q:(B,Sq,H,hd) k,v:(B,Sk,KV,hd) -> (out, lse (B,H,Sq,1) f32).

    GQA via head mapping. The per-row statistics (lse here, delta in the
    backward pass) are carried as (…, Sq, 1) columns: a block's last two
    dimensions must tile (8, 128) or span the array, so (block_q, 1) over
    (Sq, 1) compiles where a 1-wide slice of a head axis would not."""
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k

    # (B,S,H,hd) -> (B,H,S,hd) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, H, nq, nk)
    out_t, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b, h, iq, ik, G=G: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            # VMEM accumulators carried across the sequential ik dimension
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out_t.transpose(0, 2, 1, 3), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale, causal, block_q, block_k, nk):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start, k_start = iq * block_q, ik * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(jnp.asarray(run))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                              # (bq, 1)
        delta = delta_ref[0, 0]                          # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_acc[...] += jax.lax.dot(ds.astype(k.dtype), k,
                                   preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, block_q, block_k, nq):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start, k_start = iq * block_q, ik * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(jnp.asarray(run))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]                                # (bq, 1)
        delta = delta_ref[0, 0]                            # (bq, 1)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        p = jnp.exp(s - lse)                               # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bk, hd)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale                      # (bq, bk)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # (bk, hd)

    @pl.when(iq == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "scale"))
def flash_attention_bwd(q, k, v, out, lse, do, *, causal=True, scale=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        interpret=False):
    B, Sq, H, hd = q.shape
    _, Sk, KV, _ = k.shape
    G = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    nq, nk = Sq // block_q, Sk // block_k

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dot = do.transpose(0, 2, 1, 3)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[..., None]  # (B,H,Sq,1)

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k)
    q_spec = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0))
    kv_spec_q = pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, i, j, G=G: (b, h // G, j, 0))
    lse_spec = pl.BlockSpec((1, 1, block_q, 1),
                            lambda b, h, i, j: (b, h, i, 0))

    dq_t = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, nk=nk, **kw),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec_q, kv_spec_q, q_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    # dk/dv per Q-head; group-summed outside the kernel (GQA)
    q_spec_k = pl.BlockSpec((1, 1, block_q, hd), lambda b, h, j, i: (b, h, i, 0))
    kv_spec_k = pl.BlockSpec((1, 1, block_k, hd),
                             lambda b, h, j, i, G=G: (b, h // G, j, 0))
    kvh_spec = pl.BlockSpec((1, 1, block_k, hd), lambda b, h, j, i: (b, h, j, 0))
    lse_spec_k = pl.BlockSpec((1, 1, block_q, 1),
                              lambda b, h, j, i: (b, h, i, 0))
    dkh_t, dvh_t = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, nq=nq, **kw),
        grid=(B, H, nk, nq),
        in_specs=[q_spec_k, kv_spec_k, kv_spec_k, q_spec_k, lse_spec_k,
                  lse_spec_k],
        out_specs=[kvh_spec, kvh_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, Sk, hd), k.dtype),
                   jax.ShapeDtypeStruct((B, H, Sk, hd), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, hd), jnp.float32),
                        pltpu.VMEM((block_k, hd), jnp.float32)],
        interpret=interpret,
    )(qt, kt, vt, dot, lse, delta)

    dq = dq_t.transpose(0, 2, 1, 3)
    dk = dkh_t.reshape(B, KV, G, Sk, hd).sum(2).transpose(0, 2, 1, 3)
    dv = dvh_t.reshape(B, KV, G, Sk, hd).sum(2).transpose(0, 2, 1, 3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)
