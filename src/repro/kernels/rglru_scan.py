"""Pallas TPU kernel for the RG-LRU gated linear recurrence.

The gates (a, βix) are elementwise and fuse fine under XLA, so they are
computed *outside* the kernel; the kernel is the irreducibly sequential
part: h_t = a_t ⊙ h_{t-1} + gx_t over time, vectorized across the width
lanes. Grid: (batch, width_blocks, time_blocks) with the hidden state in
VMEM scratch across time blocks; within a block a fori_loop steps the
recurrence on (1, bw) vectors (VPU work; this layer is bandwidth-bound).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import RGLRU_C


def _kernel(a_ref, gx_ref, h0_ref, y_ref, hlast_ref, h_ref, *,
            bt: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = h0_ref[...].astype(jnp.float32)

    def step(t, h):
        a_t = a_ref[0, t, :].astype(jnp.float32)
        gx_t = gx_ref[0, t, :].astype(jnp.float32)
        h = a_t * h + gx_t
        y_ref[0, t, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bt, step, h_ref[0, :])
    h_ref[0, :] = h

    @pl.when(ti == nt - 1)
    def _final():
        hlast_ref[...] = h_ref[...].astype(hlast_ref.dtype)


def rglru_scan_pallas(a: jax.Array, gx: jax.Array, h0: jax.Array, *,
                      block_t: int = 128, block_w: int = 512,
                      interpret: bool = False):
    """Raw scan: h_t = a_t*h_{t-1} + gx_t. a,gx (B,S,W); h0 (B,W) f32.

    Returns (h_seq (B,S,W) in gx.dtype, h_last (B,W) f32).
    """
    B, S, W = a.shape
    bt = min(block_t, S)
    assert S % bt == 0, (S, bt)
    bw = min(block_w, W)
    assert W % bw == 0, (W, bw)
    nt, nw = S // bt, W // bw

    kernel = functools.partial(_kernel, bt=bt, nt=nt)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bw), lambda b, wi, ti: (b, ti, wi)),
            pl.BlockSpec((1, bt, bw), lambda b, wi, ti: (b, ti, wi)),
            pl.BlockSpec((1, bw), lambda b, wi, ti: (b, wi)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, bw), lambda b, wi, ti: (b, ti, wi)),
            pl.BlockSpec((1, bw), lambda b, wi, ti: (b, wi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, W), gx.dtype),
            jax.ShapeDtypeStruct((B, W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, gx, h0)
    return y, h_last


def rglru_pallas(x: jax.Array, r: jax.Array, i: jax.Array, lam: jax.Array, *,
                 h0: Optional[jax.Array] = None, interpret: bool = False):
    """Full RG-LRU (gates outside, scan kernel inside). Same semantics as
    ``ref.rglru_ref``: returns (h_seq (B,S,W), h_final (B,W) f32)."""
    B, S, W = x.shape
    log_a_base = -RGLRU_C * jax.nn.softplus(lam.astype(jnp.float32))
    rg = jax.nn.sigmoid(r.astype(jnp.float32))
    ig = jax.nn.sigmoid(i.astype(jnp.float32))
    log_a = log_a_base[None, None, :] * rg
    a = jnp.exp(log_a)
    beta = jnp.sqrt(-jnp.expm1(2.0 * log_a))
    gx = beta * (ig * x.astype(jnp.float32))
    h0f = (jnp.zeros((B, W), jnp.float32) if h0 is None
           else h0.astype(jnp.float32))
    y, h_last = rglru_scan_pallas(a.astype(x.dtype), gx.astype(jnp.float32),
                                  h0f, interpret=interpret)
    return y.astype(x.dtype), h_last
