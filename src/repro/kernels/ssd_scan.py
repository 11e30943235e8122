"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

Grid = (batch, heads, n_chunks) with the chunk axis minor-most: the recurrent
state (n, p) lives in VMEM scratch and is carried across sequential chunk
iterations — the matmul-form SSD maps the intra-chunk work onto the MXU
((L,n)@(n,L), (L,L)@(L,p), (n,L)@(L,p)) while the cross-chunk recurrence is a
rank-1 state update per chunk. This is the TPU-native adaptation of the CUDA
SSD kernel (arXiv:2405.21060): no warp shuffles — tiles + sequential grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, state,
                *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0].astype(jnp.float32)              # (L, p)
    dt = dt_ref[0, 0]                                # (1, L) f32
    da = da_ref[0, 0]                                # (1, L) f32: dt * A
    B = b_ref[0, 0].astype(jnp.float32)              # (L, n)
    C = c_ref[0, 0].astype(jnp.float32)              # (L, n)

    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = rows >= cols
    # prefix sums and the row -> column turn as matmuls with a triangle /
    # the identity: (1, L) rows are what the lane-dense blocks deliver
    nt = (((1,), (1,)), ((), ()))
    hi = jax.lax.Precision.HIGHEST
    tril = causal.astype(jnp.float32)
    cum_col = jax.lax.dot_general(tril, da, nt, precision=hi,
                                  preferred_element_type=jnp.float32)  # (L,1)
    cum_row = jax.lax.dot_general(da, tril, nt, precision=hi,
                                  preferred_element_type=jnp.float32)  # (1,L)
    dt_col = jax.lax.dot_general((rows == cols).astype(jnp.float32), dt, nt,
                                 precision=hi,
                                 preferred_element_type=jnp.float32)   # (L,1)
    # intra-chunk masked decay matrix
    seg = cum_col - cum_row                          # (L, L)
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jax.lax.dot_general(C, B, nt, preferred_element_type=jnp.float32)
    scores = scores * decay * dt                     # (L, L)
    y_diag = jax.lax.dot(scores, x, preferred_element_type=jnp.float32)

    # off-diagonal: contribution of the carried state
    y_off = jax.lax.dot(C * jnp.exp(cum_col), state[...],
                        preferred_element_type=jnp.float32)  # (L, p)

    # state update: S <- exp(sum da) * S + sum_l decay_out_l dt_l B_l x_l^T
    chunk_sum = jnp.sum(da, axis=1, keepdims=True)   # (1, 1)
    decay_out = jnp.exp(chunk_sum - cum_col)         # (L, 1)
    bw = B * (decay_out * dt_col)                    # (L, n)
    new_state = jax.lax.dot_general(bw, x, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    state[...] = state[...] * jnp.exp(chunk_sum) + new_state

    y_ref[0, 0] = (y_diag + y_off).astype(y_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_scan_fwd(x, dt, A, B, C, *, chunk: int = 128, interpret=False):
    """x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n) -> y:(b,s,h,p).

    h % g == 0 (groups broadcast to heads via the BlockSpec index map).
    Heads move out of the tiled (last two) block dimensions: x and y run
    as (b, h, s, p), B and C as (b, g, s, n), and dt and dt * A as
    (b, h, 1, s) rows, so every block ends in (chunk, p | n) or (1, chunk).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    dt32 = dt.astype(jnp.float32)
    rows = lambda v: v.transpose(0, 2, 1)[:, :, None, :]   # (b,h,1,s)
    y = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic, rep=rep: (ib, ih // rep, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda ib, ih, ic, rep=rep: (ib, ih // rep, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda ib, ih, ic: (ib, ih, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), rows(dt32),
      rows(dt32 * A.astype(jnp.float32)[None, None, :]),
      B.transpose(0, 2, 1, 3), C.transpose(0, 2, 1, 3))
    return y.transpose(0, 2, 1, 3)
