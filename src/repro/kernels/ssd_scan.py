"""Pallas TPU kernel for the Mamba-2 SSD chunked scan (G=1 groups).

Grid: (batch, head_blocks, chunks); chunks are the innermost sequential axis
so the (bh, P, N) SSD state lives in VMEM scratch across chunks. Per chunk
the kernel runs the dense intra-chunk form (MXU matmuls over Q×Q decay-
masked scores) and one state update — mirroring ``ref.ssd_chunked``.

VMEM per step (defaults Q=128, bh=8, P=64, N=128): x 64 KB + b/c 64 KB +
state 256 KB f32 + Q×Q scores 64 KB ≈ well under budget.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref, d_ref, y_ref, hout_ref,
            h_ref, *, nc: int, Q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)                    # (Q, bh, P)
    dt = dt_ref[0].astype(jnp.float32)                  # (Q, bh)
    a = -jnp.exp(alog_ref[...].astype(jnp.float32))    # (bh,)
    b = b_ref[0].astype(jnp.float32)                    # (Q, N)
    c = c_ref[0].astype(jnp.float32)                    # (Q, N)
    d = d_ref[...].astype(jnp.float32)                  # (bh,)

    da = dt * a[None, :]                                # (Q, bh)
    cum = jnp.cumsum(da, axis=0)                        # inclusive
    # intra-chunk: scores (Q,Q) shared across heads (G=1)
    scores = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    seg = cum.T[:, :, None] - cum.T[:, None, :]         # (bh, Q, Q)
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = (qi >= ki)[None]
    L = jnp.where(tri, jnp.exp(seg), 0.0)
    m = scores[None] * L * dt.T[:, None, :]             # (bh, Q, Q)
    y_diag = jnp.einsum("hqk,khp->qhp", m, x,
                        preferred_element_type=jnp.float32)
    # inter-chunk: contribution of incoming state
    h = h_ref[...]                                      # (bh, P, N) f32
    y_off = jnp.einsum("qn,hpn,qh->qhp", c, h, jnp.exp(cum),
                       preferred_element_type=jnp.float32)
    # state update
    decay_to_end = jnp.exp(cum[-1:, :] - cum)           # (Q, bh)
    s_new = jnp.einsum("kh,kn,khp->hpn", dt * decay_to_end, b, x,
                       preferred_element_type=jnp.float32)
    h_ref[...] = h * jnp.exp(cum[-1, :])[:, None, None] + s_new

    y = y_diag + y_off + x * d[None, :, None]
    y_ref[0] = y.astype(y_ref.dtype)
    hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def ssd_pallas(x: jax.Array, dt: jax.Array, a_log: jax.Array,
               b: jax.Array, c: jax.Array, d: jax.Array, *,
               h0: Optional[jax.Array] = None, chunk: int = 128,
               block_heads: int = 8, interpret: bool = False):
    """Same semantics as ``ref.ssd_chunked`` restricted to G=1, h0=None.

    x (B,S,H,P); dt (B,S,H); a_log,d (H,); b,c (B,S,1,N).
    Returns (y (B,S,H,P), h_final (B,H,P,N) f32).
    """
    assert b.shape[2] == 1, "pallas ssd kernel supports G=1 (mamba2)"
    assert h0 is None, "h0 handled by the jnp path"
    B, S, H, P = x.shape
    N = b.shape[3]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    bh = min(block_heads, H)
    assert H % bh == 0, (H, bh)
    nh = H // bh
    b2 = b[:, :, 0, :]
    c2 = c[:, :, 0, :]

    kernel = functools.partial(_kernel, nc=nc, Q=Q)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec((1, Q, bh, P), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, Q, bh), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((bh,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, Q, N), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((bh,), lambda bi, hi, ci: (hi,)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, bh, P), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, bh, P, N), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bh, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, a_log, b2, c2, d)
    return y, h_final
