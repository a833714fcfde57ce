"""Pallas TPU kernel: GQA flash-decode over a PAGED KV cache.

vLLM-style paged attention, TPU-native: the KV arena is a shared page pool
``[n_pages, Hkv, page_size, hd]`` and each sequence names its pages through
a ``[B, max_pages]`` page table. The table (plus per-sequence query
positions) rides in as scalar-prefetch operands — available before the
kernel body runs — so each grid step's BlockSpec index_map dereferences
``page_table[b, j]`` to DMA exactly that sequence's j-th physical page into
VMEM. No contiguous gather ever materializes in HBM; the indirection is
free address arithmetic on the DMA descriptor.

The arena may also be the model's whole stacked cache ``[L, n_pages, Hkv,
page_size, W]`` with the layer index as a third scalar-prefetch operand:
the index_map then reads layer ``l``'s pages straight out of it, so a
layer loop that carries the whole arena never slices a layer's slab out
(DESIGN.md §3). A 4-D arena is the one-layer case. The arena's minor dim
may be wider than the queries' head_dim (the model pads it to whole
128-lane rows): the queries are zero-padded to it, so the padding lanes
add nothing to q.k, and the output's padding lanes are dropped.

Grid: (batch, kv_heads, max_pages) — pages innermost so the online-softmax
scratch state (m, l, acc) accumulates sequentially per (b, h), exactly as in
decode_attention.py; unused table entries (-1) are masked, and their DMA is
clamped to page 0 (harmless: the mask zeroes the contribution).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.models.layers import lane_pad

NEG_INF = -1e30


def _kernel(pt_ref, qpos_ref, _layer_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size, scale):
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [g, W]
    k = k_ref[0, 0].astype(jnp.float32)          # [psz, W]
    v = v_ref[0, 0].astype(jnp.float32)          # [psz, W]
    qp = qpos_ref[b]                             # scalar int32
    page = pt_ref[b, j]                          # physical page id, -1 unused

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = j * page_size + jax.lax.iota(jnp.int32, page_size)   # logical pos
    keep = (page >= 0) & (pos <= qp)
    s = jnp.where(keep[None, :], s, NEG_INF)     # [g, psz]

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    # fully-masked page: m_new == NEG_INF makes exp(s - m_new) == 1 for
    # masked lanes — re-mask so they contribute nothing.
    p = jnp.where(keep[None, :], p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _verify_kernel(pt_ref, qpos_ref, _layer_ref, q_ref, k_ref, v_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, page_size, groups, scale):
    """Multi-query generalization of ``_kernel``: R = C*groups query rows
    per (b, h) block, row r at logical position q_start[b] + r // groups —
    the causal staircase of a speculative verify window (DESIGN.md §8)."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)          # [R, W]
    k = k_ref[0, 0].astype(jnp.float32)          # [psz, W]
    v = v_ref[0, 0].astype(jnp.float32)          # [psz, W]
    qp = qpos_ref[b]                             # first query's position
    page = pt_ref[b, j]                          # physical page id, -1 unused
    R = q.shape[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (R, page_size), 1)            # logical KV position
    row_pos = qp + jax.lax.broadcasted_iota(
        jnp.int32, (R, page_size), 0) // groups  # this row's query position
    keep = (page >= 0) & (pos <= row_pos)        # [R, psz]
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    # fully-masked page: m_new == NEG_INF makes exp(s - m_new) == 1 for
    # masked lanes — re-mask so they contribute nothing.
    p = jnp.where(keep, p, 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nj - 1)
    def _fin():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def _layer_arena(k_pages, v_pages, layer):
    """(k, v, layer vector) over a stacked [L,P,Hkv,psz,W] arena; a 4-D
    arena is one layer, read as layer 0 of a stack of one."""
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
    return k_pages, v_pages, jnp.asarray(layer, jnp.int32).reshape(1)


def _page_spec(psz, w):
    """Layer ``ly[0]``, physical page ``pt[b, j]`` (unused entries clamped
    to page 0 and masked in the body), KV head ``h``."""
    return pl.BlockSpec((pl.squeezed, 1, 1, psz, w),
                        lambda b, h, j, pt, qp, ly: (
                            ly[0], jnp.maximum(pt[b, j], 0), h, 0, 0))


def paged_verify_attention_kernel(q, k_pages, v_pages, page_table, q_start,
                                  layer=None, interpret: bool = False):
    """q: [B,C,Hq,hd] — C verify queries at positions q_start[b]+i;
    k/v_pages: [P,Hkv,psz,W], or the stacked [L,P,Hkv,psz,W] arena with
    ``layer`` naming the layer to read; page_table: [B,maxp] int32 (-1 =
    unused); q_start: [B] int32. Returns [B,C,Hq,hd]. Same contract as
    layers.paged_verify_attention (the jnp oracle)."""
    B, C, Hq, hd = q.shape
    k_pages, v_pages, layer = _layer_arena(k_pages, v_pages, layer)
    _, _, Hkv, psz, w = k_pages.shape
    maxp = page_table.shape[1]
    g = Hq // Hkv
    R = C * g
    qg = lane_pad(q.reshape(B, C, Hkv, g, hd)
                   .transpose(0, 2, 1, 3, 4).reshape(B, Hkv, R, hd), w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, R, w),
                         lambda b, h, j, pt, qp, ly: (b, h, 0, 0)),
            _page_spec(psz, w),
            _page_spec(psz, w),
        ],
        out_specs=pl.BlockSpec((1, 1, R, w),
                               lambda b, h, j, pt, qp, ly: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, 128), jnp.float32),   # running max
            pltpu.VMEM((R, 128), jnp.float32),   # running denom
            pltpu.VMEM((R, w), jnp.float32),     # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_verify_kernel, page_size=psz, groups=g,
                          scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, R, w), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), q_start.astype(jnp.int32), layer,
      qg, k_pages, v_pages)
    return (out[..., :hd].reshape(B, Hkv, C, g, hd)
            .transpose(0, 2, 1, 3, 4).reshape(B, C, Hq, hd))


def paged_decode_attention_kernel(q, k_pages, v_pages, page_table, q_pos,
                                  layer=None, interpret: bool = False):
    """q: [B,Hq,hd]; k/v_pages: [P,Hkv,psz,W], or the stacked
    [L,P,Hkv,psz,W] arena with ``layer`` naming the layer to read;
    page_table: [B,maxp] int32 (-1 = unused); q_pos: [B] int32 — newest
    token's logical position. Returns [B,Hq,hd]. Same contract as
    layers.paged_decode_attention."""
    B, Hq, hd = q.shape
    k_pages, v_pages, layer = _layer_arena(k_pages, v_pages, layer)
    _, _, Hkv, psz, w = k_pages.shape
    maxp = page_table.shape[1]
    g = Hq // Hkv
    qg = lane_pad(q.reshape(B, Hkv, g, hd), w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, maxp),
        in_specs=[
            pl.BlockSpec((1, 1, g, w),
                         lambda b, h, j, pt, qp, ly: (b, h, 0, 0)),
            _page_spec(psz, w),
            _page_spec(psz, w),
        ],
        out_specs=pl.BlockSpec((1, 1, g, w),
                               lambda b, h, j, pt, qp, ly: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),   # running max
            pltpu.VMEM((g, 128), jnp.float32),   # running denom
            pltpu.VMEM((g, w), jnp.float32),     # running numerator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=psz, scale=hd ** -0.5),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, w), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), q_pos.astype(jnp.int32), layer,
      qg, k_pages, v_pages)
    return out[..., :hd].reshape(B, Hq, hd)
