"""jit'd dispatch wrappers for the Pallas kernels.

On a TPU the kernels compile with Mosaic; on any other backend they run in
interpret mode, where the kernel body executes as plain JAX ops, validating
the same program logic.
Backward passes: flash attention has a full Pallas bwd; ssd/rmsnorm use
custom_vjp with an XLA bwd over the ref (kernel accelerates fwd, bwd is
recompute — documented in docs/DESIGN.md §1, kernels layer).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import event_select as es
from repro.kernels import flash_attention as fa
from repro.kernels import ref
from repro.kernels import rmsnorm as rn
from repro.kernels import ssd_scan as ss


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# event select: the Pallas kernel for 32-bit event matrices (the jit fleet
# engine holds f64 state and reduces it in XLA instead; see fleet_jit)
# ---------------------------------------------------------------------------
def event_select(ev):
    """(n, m) candidate-event times, inf = masked -> (min_t (n,), argmin
    (n,) int32), ties broken by lowest column. Not differentiable."""
    return es.event_select_fwd(ev, interpret=_interpret())


# ---------------------------------------------------------------------------
# flash attention with custom vjp (Pallas fwd + Pallas bwd)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, block_q=fa.DEFAULT_BLOCK_Q,
                    block_k=fa.DEFAULT_BLOCK_K):
    out, _ = fa.flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                                    block_k=block_k, interpret=_interpret())
    return out


def _fa_fwd(q, k, v, causal, block_q, block_k):
    out, lse = fa.flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                                      block_k=block_k, interpret=_interpret())
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, block_q, block_k, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = fa.flash_attention_bwd(q, k, v, out, lse, do, causal=causal,
                                        block_q=block_q, block_k=block_k,
                                        interpret=_interpret())
    return dq, dk, dv


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# ---------------------------------------------------------------------------
# SSD scan: Pallas fwd, ref-recompute bwd
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd_scan(x, dt, A, B, C, chunk=128):
    return ss.ssd_scan_fwd(x, dt, A, B, C, chunk=chunk,
                           interpret=_interpret())


def _ssd_fwd(x, dt, A, B, C, chunk):
    y = ss.ssd_scan_fwd(x, dt, A, B, C, chunk=chunk, interpret=_interpret())
    return y, (x, dt, A, B, C)


def _ssd_bwd(chunk, res, dy):
    x, dt, A, B, C = res
    _, vjp = jax.vjp(lambda *a: ref.ssd_scan_ref(*a, chunk=chunk),
                     x, dt, A, B, C)
    return vjp(dy)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)


# ---------------------------------------------------------------------------
# RMSNorm: Pallas fwd, analytic bwd (jnp)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, scale, eps=1e-5):
    return rn.rmsnorm_fwd(x, scale, eps=eps, interpret=_interpret())


def _rn_fwd(x, scale, eps):
    return rn.rmsnorm_fwd(x, scale, eps=eps, interpret=_interpret()), (x, scale)


def _rn_bwd(eps, res, dy):
    x, scale = res
    _, vjp = jax.vjp(lambda xx, ss_: ref.rmsnorm_ref(xx, ss_, eps=eps), x, scale)
    return vjp(dy)


rmsnorm.defvjp(_rn_fwd, _rn_bwd)
