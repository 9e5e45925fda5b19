"""Pallas TPU flash attention (forward) with explicit BlockSpec VMEM tiling.

Grid: (batch, q_heads, q_blocks, kv_blocks); the kv dimension is the
innermost ("arbitrary") axis so the online-softmax state lives in VMEM
scratch across kv steps. GQA is expressed in the k/v index maps (h // G).
Causal / local-window blocks that cannot contribute are skipped via
``pl.when`` (MXU work saved; the block loads are bounded by the BlockSpec).

Validated against ``ref.attention_ref`` in interpret mode on CPU; on TPU the
same kernel compiles to MXU matmuls with bq×Dh + 2·bk×Dh + bq×bk VMEM
residency per step (defaults: bq=bk=256, Dh≤256 → ≤ ~1.2 MB ≪ 16 MB VMEM).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import NEG_INF


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, window: int, kv_len: int, scale: float,
            bq: int, bk: int, nk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = i * bq
    k_start = j * bk
    needed = k_start < kv_len
    if causal:
        needed &= k_start <= q_start + bq - 1
    if window and window > 0:
        needed &= (k_start + bk - 1) > q_start - window

    @pl.when(needed)
    def _body():
        qb = q_ref[0, :, 0, :].astype(jnp.float32) * scale      # (bq, Dh)
        kb = k_ref[0, :, 0, :].astype(jnp.float32)              # (bk, Dh)
        vb = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kv_pos < kv_len
        if causal:
            mask &= kv_pos <= q_pos
        if window and window > 0:
            mask &= kv_pos > q_pos - window
        s = s + jnp.where(mask, 0.0, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, vb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: Optional[float] = None,
                    block_q: int = 256, block_kv: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q (B,Sq,Hq,Dh); k,v (B,Skv,Hkv,Dh) -> (B,Sq,Hq,Dh)."""
    B, Sq, Hq, Dh = q.shape
    _, Skv, Hkv, _ = k.shape
    assert Hq % Hkv == 0
    G = Hq // Hkv
    scale = scale if scale is not None else Dh ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_kv, Skv)
    sq_p = -(-Sq // bq) * bq
    skv_p = -(-Skv // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, sq_p - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, skv_p - Skv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, skv_p - Skv), (0, 0), (0, 0)))
    nq, nk = sq_p // bq, skv_p // bk

    kernel = functools.partial(_kernel, causal=causal, window=window,
                               kv_len=Skv, scale=scale, bq=bq, bk=bk, nk=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1, Dh), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, Dh), lambda b, h, i, j: (b, j, h // G, 0)),
            pl.BlockSpec((1, bk, 1, Dh), lambda b, h, i, j: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, Dh), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, sq_p, Hq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :Sq]
