"""Operations and bytes of the served steps and kernels, from shapes.

These count what the algorithm needs, not what an implementation happens
to do: a decode row attends over its own context only, whatever the padded
batch bucket or page-table width; a matrix multiply of [m, k] by [k, n]
costs 2mkn operations. Bytes are float32 (4 per element) unless the caller
says otherwise. ``Arch`` is ``references.llama_dense.Arch``.
"""
from __future__ import annotations

from typing import Iterable

F32 = 4


def matmul_flops_per_token(a) -> float:
    """Every weight matrix once per token: the four attention projections,
    the three MLP matrices in each layer, and the output head (the
    embedding is a lookup)."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    D, F = a.d_model, a.d_ff
    per_layer = D * q + 2 * D * kv + q * D + 3 * D * F
    return 2.0 * (a.layers * per_layer + D * a.vocab)


def attn_flops(a, n_keys: float, layers: int = None) -> float:
    """One query row over ``n_keys`` keys in every layer: QK^T and PV, each
    2 * heads * head_dim per key."""
    return 4.0 * (a.layers if layers is None else layers) * a.heads \
        * a.head_dim * n_keys


def decode_step_flops(a, contexts: Iterable[int]) -> float:
    """One decode step; ``contexts`` are the active rows' context lengths
    including the new token."""
    ctx = list(contexts)
    return len(ctx) * matmul_flops_per_token(a) + attn_flops(a, sum(ctx))


def causal_keys(start: int, n: int) -> float:
    """Keys seen by queries at positions start..start+n-1 of one row."""
    return n * start + n * (n + 1) / 2.0


def chunk_step_flops(a, start: int, n: int) -> float:
    """One prefill chunk of ``n`` tokens after ``start`` cached ones."""
    return n * matmul_flops_per_token(a) + attn_flops(a, causal_keys(start, n))


def paged_decode_kernel_cost(a, contexts: Iterable[int], shards: int = 1):
    """(flops, bytes) of one paged decode attention call (one layer) on one
    chip holding 1/shards of the heads: read each row's K and V for its
    context, read the query and write the output."""
    ctx = list(contexts)
    H, K = a.heads / shards, a.kv_heads / shards
    flops = 4.0 * H * a.head_dim * sum(ctx)
    byts = F32 * a.head_dim * (2.0 * K * sum(ctx) + 2.0 * H * len(ctx))
    return flops, byts


def flash_chunk_kernel_cost(a, start: int, n: int, shards: int = 1):
    """(flops, bytes) of one flash prefill chunk call (one layer): causal
    attention of n queries over start+n keys; read K and V up to the
    chunk's end, read the queries and write the output."""
    H, K = a.heads / shards, a.kv_heads / shards
    flops = 4.0 * H * a.head_dim * causal_keys(start, n)
    byts = F32 * a.head_dim * (2.0 * K * (start + n) + 2.0 * H * n)
    return flops, byts


def roofline_s(flops: float, byts: float, peak_flops: float,
               peak_bw: float):
    """Least time the chip could take, and which bound sets it."""
    tc, tm = flops / peak_flops, byts / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
