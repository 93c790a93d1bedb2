"""Pallas TPU flash-attention forward kernel (GQA, causal, window, kv_len).

Schedule: grid (B, H, Sq/BQ, Skv/BK) — the trailing (kv) grid dimension is
sequential on TPU, so the (acc, m, l) online-softmax state lives in VMEM
scratch and persists across kv steps; the output block is written once, on
the last kv step.  Causal/window masking skips whole kv blocks via pl.when
(the MXU never sees them); GQA folds the q-head group into the kv index
map.  All matmuls hit the MXU in f32 accumulation.

Layout: (B, H, S, Dh) — heads-major so q/k/v blocks are (BQ|BK, Dh) tiles,
lane-aligned for Dh ∈ {64, 96, 128, 160, 256}.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(
    # scalar-prefetch operands (SMEM)
    qoff_ref,  # (1,) int32: absolute position of q block row 0
    kvlen_ref,  # (1,) int32: valid kv length
    # tensor operands (VMEM blocks)
    q_ref,  # (1, 1, BQ, Dh)
    k_ref,  # (1, 1, BK, Dh)
    v_ref,  # (1, 1, BK, Dh)
    o_ref,  # (1, 1, BQ, Dh)
    # scratch
    acc_ref,  # (BQ, Dh) f32
    m_ref,  # (BQ, 128) f32  (lane-padded)
    l_ref,  # (BQ, 128) f32
    *,
    bq: int,
    bk: int,
    n_kv_blocks: int,
    causal: bool,
    window: Optional[int],
    cap: Optional[float],
    scale: float,
):
    qb = pl.program_id(2)
    kvb = pl.program_id(3)

    @pl.when(kvb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qoff_ref[0] + qb * bq  # absolute position of first q row
    k_start = kvb * bk
    kv_len = kvlen_ref[0]

    # block-level skip: entirely-masked kv blocks never touch the MXU
    live = k_start < kv_len
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + bq - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + bk - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (BQ, Dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (BK, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (BQ, BK)
        if cap is not None:
            s = cap * jnp.tanh(s / cap)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < kv_len
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (BQ, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (BQ, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (BQ, BK)
        alpha = jnp.exp(m_prev - m_new)  # (BQ, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kvb == n_kv_blocks - 1)
    def _finalize():
        lse = l_ref[:, :1]
        o = acc_ref[...] / jnp.maximum(lse, 1e-37)
        o_ref[0, 0] = o.astype(o_ref.dtype)


def flash_attention_bhsd(
    q: jax.Array,  # (B, H, Sq, Dh)
    k: jax.Array,  # (B, Kh, Skv, Dh)
    v: jax.Array,
    *,
    causal: bool = True,
    q_offset=0,
    kv_len=None,
    window: Optional[int] = None,
    cap: Optional[float] = None,
    bq: int = 256,
    bk: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, h, sq, dh = q.shape
    kh, skv = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(bq, sq)
    bk = min(bk, skv)
    assert sq % bq == 0 and skv % bk == 0, "ops.py pads to block multiples"
    n_kv_blocks = skv // bk
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    kernel = functools.partial(
        _flash_kernel,
        bq=bq,
        bk=bk,
        n_kv_blocks=n_kv_blocks,
        causal=causal,
        window=window,
        cap=cap,
        scale=float(1.0 / np.sqrt(dh)),
    )

    grid = (b, h, sq // bq, n_kv_blocks)

    qoff = jnp.asarray(q_offset, jnp.int32).reshape(1)
    klen = jnp.asarray(skv if kv_len is None else kv_len, jnp.int32).reshape(1)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, dh), lambda bb, hh, qb, kb, *_: (bb, hh, qb, 0)),
                pl.BlockSpec((1, 1, bk, dh), lambda bb, hh, qb, kb, *_: (bb, hh // g if g > 1 else hh, kb, 0)),
                pl.BlockSpec((1, 1, bk, dh), lambda bb, hh, qb, kb, *_: (bb, hh // g if g > 1 else hh, kb, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, dh), lambda bb, hh, qb, kb, *_: (bb, hh, qb, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, dh), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qoff, klen, q, k, v)
