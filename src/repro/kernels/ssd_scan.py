"""Mamba2 SSD chunked-scan Pallas TPU kernel.

TPU-native layout of the state-space-duality algorithm: the grid is
(batch, heads, chunks) with chunks INNERMOST — Pallas TPU grids execute
sequentially, so the (P, N) recurrent state lives in VMEM scratch across
chunk steps, exactly the HBM->VMEM residency the SSD recurrence wants.
Per chunk: intra-chunk quadratic term on the MXU, state emit/consume as
two more (Q,·)x(·,·) matmuls.  Replaces the GPU warp-parallel scan with
a VMEM-resident sequential chunk walk (DESIGN.md §Hardware adaptation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_HI = jax.lax.Precision.HIGHEST


def _kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, h0_ref,
            y_ref, hout_ref, state_scr, *, nc: int, Q: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = h0_ref[0, 0].astype(jnp.float32)       # (P, N)

    x = x_ref[0, 0, 0].astype(jnp.float32)       # (Q, P)
    dt = dt_ref[0, 0, 0]                         # (Q, 1)
    dA = da_ref[0, 0, 0]                         # (Q, 1) = dt * A
    Bm = b_ref[0, 0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0, 0].astype(jnp.float32)         # (Q, N)

    # in-chunk prefix sums as products with the lower-triangular ones
    # matrix (Mosaic cannot lower cumsum); full-f32 passes keep the
    # decay exponents exact to f32 rounding
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    lower = tri.astype(jnp.float32)
    cums = jnp.dot(lower, dA, precision=_HI,
                   preferred_element_type=jnp.float32)            # (Q, 1)
    cums_row = jax.lax.dot_general(dA, lower, (((0,), (1,)), ((), ())),
                                   precision=_HI,
                                   preferred_element_type=jnp.float32)
    total = jnp.sum(dA, axis=0, keepdims=True)                    # (1, 1)
    xdt = x * dt                                 # (Q, P)

    # intra-chunk: (C B^T ∘ L) @ (x dt)
    Lmat = jnp.where(tri, jnp.exp(cums - cums_row), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    y = jnp.dot(scores * Lmat, xdt, preferred_element_type=jnp.float32)

    # inter-chunk: C_t exp(cums_t) . h_prev
    state = state_scr[...]                       # (P, N)
    y += jax.lax.dot_general(Cm * jnp.exp(cums), state,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update: h <- exp(cums_Q) h + (x dt decay_to_end)^T B
    contrib = jax.lax.dot_general(xdt * jnp.exp(total - cums), Bm,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    state_scr[...] = state * jnp.exp(total) + contrib

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = state_scr[...].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, h0=None,
             interpret: bool = False):
    """SSD scan.  x: (b, l, h, p); dt: (b, l, h) (post-softplus);
    A: (h,) negative; B, C: (b, l, n); h0: (b, h, p, n) or None.

    Returns (y (b, l, h, p), h_final (b, h, p, n)).
    """
    b, l, h, p = x.shape
    n = B.shape[-1]
    Q = min(chunk, l)
    pad = (-l) % Q
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    L = l + pad
    nc = L // Q
    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), jnp.float32)

    # TPU-friendly layouts: dt and dt*A as (Q, 1) column tiles per chunk
    xr = x.transpose(0, 2, 1, 3).reshape(b, h, nc, Q, p)
    dtr = dt.astype(jnp.float32).transpose(0, 2, 1).reshape(b, h, nc, Q, 1)
    dar = dtr * A.astype(jnp.float32).reshape(1, h, 1, 1, 1)
    br = B.reshape(b, nc, Q, n)
    cr = C.reshape(b, nc, Q, n)
    col = pl.BlockSpec((1, 1, 1, Q, 1), lambda i, j, c: (i, j, c, 0, 0))

    grid = (b, h, nc)
    y, hout = pl.pallas_call(
        functools.partial(_kernel, nc=nc, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, p), lambda i, j, c: (i, j, c, 0, 0)),
            col,
            col,
            pl.BlockSpec((1, 1, Q, n), lambda i, j, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, n), lambda i, j, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, c: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, p), lambda i, j, c: (i, j, c, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j, c: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, nc, Q, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, dar, br, cr, h0)

    y = y.reshape(b, h, L, p).transpose(0, 2, 1, 3)[:, :l]
    return y, hout
