"""Fused masked min-reduction + argmin event-select Pallas kernel.

One lockstep round of the fleet engines reduces an `(n, m)` candidate-event
matrix (revocation timers ++ join timers, `inf` = masked/disarmed) to the
per-trajectory next event: its time and its column. Fusing the min and the
tie-broken argmin into one row-blocked pass keeps the event matrix in VMEM
for a single HBM round-trip; ties resolve to the lowest column index
(NumPy `argmin` semantics, which the parity contract in docs/DESIGN.md §2
pins across both fleet engines). All-masked rows return (`inf`, 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# XLA lays a 1-D f32/i32 vector out in tiles of 1024, and Mosaic requires
# each output block to match that layout
DEFAULT_BLOCK_ROWS = 1024


def _event_select_kernel(ev_ref, t_ref, i_ref):
    ev = ev_ref[...]
    m = ev.shape[1]
    mn = jnp.min(ev, axis=1)
    cols = jax.lax.broadcasted_iota(jnp.int32, ev.shape, 1)
    # lowest column attaining the min; all-masked (all-inf) rows hit the
    # `inf == inf` branch on every column and resolve to 0
    arg = jnp.min(jnp.where(ev == mn[:, None], cols, m), axis=1)
    t_ref[...] = mn.astype(t_ref.dtype)
    i_ref[...] = jnp.where(arg == m, 0, arg).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def event_select_fwd(ev, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                     interpret=False):
    """ev: (n, m) candidate event times, inf = masked.

    Returns `(t, i)`: per-row min time (n,) and its tie-broken-low column
    index (n,) int32.
    """
    n, m = ev.shape
    br = min(block_rows, max(n, 1))
    pad = (-n) % br
    evf = jnp.pad(ev, ((0, pad), (0, 0)),
                  constant_values=jnp.inf) if pad else ev
    nblocks = evf.shape[0] // br
    t, i = pl.pallas_call(
        _event_select_kernel,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((br, m), lambda b: (b, 0))],
        out_specs=[pl.BlockSpec((br,), lambda b: (b,)),
                   pl.BlockSpec((br,), lambda b: (b,))],
        out_shape=[jax.ShapeDtypeStruct((evf.shape[0],), ev.dtype),
                   jax.ShapeDtypeStruct((evf.shape[0],), jnp.int32)],
        interpret=interpret,
    )(evf)
    if pad:
        t, i = t[:n], i[:n]
    return t, i
