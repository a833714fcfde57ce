"""Core transformer layers: RMSNorm, RoPE, GQA attention (full / banded /
chunked-flash / decode-with-cache), gated MLP.

All functions are pure jnp and lower under pjit/GSPMD on any backend; the
Pallas kernels in ``repro.kernels`` are drop-in replacements for the hot
paths (see ``repro.kernels.ops``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def rms_norm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------- RoPE

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: [..., S, H, hd]; positions: broadcastable to [..., S]."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # [hd/2]
    ang = positions[..., None].astype(jnp.float32) * freqs   # [..., S, hd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # [..., S, 1, hd/2]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- masks

def band_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray, causal: bool,
              window: Optional[int]) -> jnp.ndarray:
    """[..., Sq, Sk] boolean keep-mask from absolute positions."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    m = jnp.ones(d.shape, dtype=bool)
    if causal:
        m &= d >= 0
    if window is not None:
        m &= d < window
    return m


# ---------------------------------------------------------------- attention

def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
              mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Plain softmax attention. q: [B,Sq,Hq,hd], k/v: [B,Sk,Hkv,hd].

    GQA: Hq must be a multiple of Hkv. mask: None, [Sq,Sk] or [B,Sq,Sk]
    (True = keep). Softmax in fp32.
    """
    B, Sq, Hq, hd = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32)
    scores *= hd ** -0.5
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v)
    return out.reshape(B, Sq, Hq, hd)


def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                      causal: bool = True, window: Optional[int] = None,
                      q_chunk: int = 512, k_chunk: int = 1024) -> jnp.ndarray:
    """Flash-style attention in pure jnp: O(chunk) memory via online softmax.

    This is the XLA-portable long-sequence path (the Pallas flash_prefill
    kernel implements the same contraction for TPU). Shapes as ``attention``;
    q_pos/k_pos: [Sq]/[Sk] absolute positions for the band mask.
    """
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = Hq // Hkv
    nq = -(-Sq // q_chunk)
    nk = -(-Sk // k_chunk)
    pq = nq * q_chunk - Sq
    pk = nk * k_chunk - Sk
    qf = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kf = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vf = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qpf = jnp.pad(q_pos, (0, pq), constant_values=-(10 ** 9))
    kpf = jnp.pad(k_pos, (0, pk), constant_values=10 ** 9)
    qf = qf.reshape(B, nq, q_chunk, Hkv, g, hd)
    kf = kf.reshape(B, nk, k_chunk, Hkv, hd)
    vf = vf.reshape(B, nk, k_chunk, Hkv, hd)
    qpf = qpf.reshape(nq, q_chunk)
    kpf = kpf.reshape(nk, k_chunk)
    scale = hd ** -0.5

    def q_step(_, qi):
        qc, qp = qi  # [B,qc,Hkv,g,hd], [qc]

        def k_step(carry, ki):
            m, l, acc = carry
            kc, vc, kp = ki
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qc, kc).astype(jnp.float32) * scale
            keep = band_mask(qp, kp, causal, window)  # [qc,kc]
            s = jnp.where(keep[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc).astype(jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0),
            (kf.swapaxes(0, 1), vf.swapaxes(0, 1), kpf))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)  # [B,Hkv,g,qc,hd]

    _, outs = jax.lax.scan(q_step, None, (qf.swapaxes(0, 1), qpf))
    # outs: [nq, B, Hkv, g, qc, hd] -> [B, Sq, Hq, hd]
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, nq * q_chunk, Hq, hd)
    return out[:, :Sq]


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     kv_pos: jnp.ndarray, q_pos: jnp.ndarray,
                     window: Optional[int] = None) -> jnp.ndarray:
    """Single-token decode over a (possibly ring-buffer) KV cache.

    q: [B,Hq,hd] (new token, already RoPE'd); k/v_cache: [B,Hkv,Sbuf,hd]
    (RoPE'd at absolute positions at write time); kv_pos: [B,Sbuf] absolute
    position per slot, -1 = empty; q_pos: [B].
    """
    B, Hq, hd = q.shape
    Hkv, Sbuf = k_cache.shape[1], k_cache.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Hkv, g, hd)
    s = jnp.einsum("bhgd,bhsd->bhgs", qg, k_cache).astype(jnp.float32)
    s *= hd ** -0.5
    keep = kv_pos >= 0
    keep &= kv_pos <= q_pos[:, None]
    if window is not None:
        keep &= q_pos[:, None] - kv_pos < window
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgs,bhsd->bhgd", p, v_cache)
    return out.reshape(B, Hq, hd)


def chunk_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                           v_cache: jnp.ndarray, kv_pos: jnp.ndarray,
                           q_pos: jnp.ndarray,
                           window: Optional[int] = None) -> jnp.ndarray:
    """Prompt-chunk attention over a KV cache buffer: the multi-query-token
    generalization of ``decode_attention`` (and the jnp oracle of the Pallas
    ``flash_prefill_chunk_kernel``).

    q: [B,C,Hq,hd] — one prompt chunk, RoPE'd at absolute positions
    q_pos [B,C]; k/v_cache: [B,Hkv,Sbuf,hd] with the chunk's own KV already
    written; kv_pos: [B,Sbuf] absolute position per slot, -1 = empty.
    """
    d = q_pos[:, :, None] - kv_pos[:, None, :]          # [B,C,Sbuf]
    keep = (kv_pos[:, None, :] >= 0) & (d >= 0)
    if window is not None:
        keep &= d < window
    return attention(q, k_cache.swapaxes(1, 2), v_cache.swapaxes(1, 2), keep)


def lane_pad(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the last dim of ``x`` to ``width`` (the KV arena's lane
    width, ``model.init_paged_cache``)."""
    pad = width - x.shape[-1]
    return x if pad == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1)
                                      + [(0, pad)])


def gather_pages(pages: jnp.ndarray, page_table: jnp.ndarray,
                 layer=None) -> jnp.ndarray:
    """pages: [P,Hkv,psz,W], or the stacked [L,P,Hkv,psz,W] arena with
    ``layer`` naming the layer to read (one gather — no layer slab is
    sliced out first); page_table: [B,maxp] (-1 = unused, gathered as page
    0 and masked by the caller via kv positions). W is the arena's lane
    width: head_dim and its zero padding, which callers slice off.
    Returns the contiguous view [B,Hkv,maxp*psz,W]."""
    B, maxp = page_table.shape
    Hkv, psz, W = pages.shape[-3:]
    idx = jnp.maximum(page_table, 0)
    gathered = pages[idx] if layer is None else pages[layer, idx]
    # gathered: [B,maxp,Hkv,psz,W]
    return (gathered.transpose(0, 2, 1, 3, 4)
            .reshape(B, Hkv, maxp * psz, W))


def paged_kv_positions(page_table: jnp.ndarray, page_size: int) -> jnp.ndarray:
    """Logical token position per gathered KV slot, -1 where the page-table
    entry is unused — the paged analogue of the ring cache's kv_pos array."""
    B, maxp = page_table.shape
    pos = jnp.arange(maxp * page_size, dtype=jnp.int32)
    valid = jnp.repeat(page_table >= 0, page_size, axis=1)
    return jnp.where(valid, pos[None, :], -1)


def paged_decode_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, page_table: jnp.ndarray,
                           q_pos: jnp.ndarray, layer=None) -> jnp.ndarray:
    """Single-token decode over a PAGED KV cache (pure-jnp oracle for the
    Pallas kernel in repro.kernels.paged_attention).

    q: [B,Hq,hd] (RoPE'd); k/v_pages: [P,Hkv,psz,W] — the shared page arena,
    written at absolute positions, W >= hd (lanes past hd are the arena's
    padding and are not read); page_table: [B,maxp] physical page id per
    logical page, -1 = unused; q_pos: [B] position of the newest token;
    layer: None, or the layer to read when k/v_pages are the stacked
    [L,P,Hkv,psz,W] arena. Attends over logical positions 0..q_pos (paged
    caches are append-only — no ring wrap, so no sliding window here;
    windowed archs keep the ring-slot path, DESIGN.md §3 adaptation #2).
    """
    psz, hd = k_pages.shape[-2], q.shape[-1]
    kc = gather_pages(k_pages, page_table, layer)[..., :hd]
    vc = gather_pages(v_pages, page_table, layer)[..., :hd]
    kv_pos = paged_kv_positions(page_table, psz)
    return decode_attention(q, kc, vc, kv_pos, q_pos)


def paged_verify_attention(q: jnp.ndarray, k_pages: jnp.ndarray,
                           v_pages: jnp.ndarray, page_table: jnp.ndarray,
                           q_start: jnp.ndarray, layer=None) -> jnp.ndarray:
    """Multi-token verify attention over a PAGED KV cache: the k-query
    generalization of ``paged_decode_attention`` (pure-jnp oracle for the
    Pallas kernel in repro.kernels.paged_attention), used by speculative
    decoding's draft-verify step (DESIGN.md §8).

    q: [B,C,Hq,hd] — the C=depth+1 verify queries, RoPE'd at absolute
    positions ``q_start[b]+i``; k/v_pages: [P,Hkv,psz,W] with the verify
    window's own KV already scattered in; page_table: [B,maxp] physical
    page per logical page, -1 = unused; q_start: [B] position of the first
    verify query; layer: as in ``paged_decode_attention``. Query i attends
    over logical positions 0..q_start+i (causal within the speculative
    window, full prefix before it).
    """
    psz, hd = k_pages.shape[-2], q.shape[-1]
    C = q.shape[1]
    kc = gather_pages(k_pages, page_table, layer)[..., :hd]
    vc = gather_pages(v_pages, page_table, layer)[..., :hd]
    kv_pos = paged_kv_positions(page_table, psz)
    q_pos = q_start[:, None] + jnp.arange(C, dtype=q_start.dtype)
    return chunk_decode_attention(q, kc, vc, kv_pos, q_pos)


# ---------------------------------------------------------------- MLP

def gated_mlp(x: jnp.ndarray, wg: jnp.ndarray, wu: jnp.ndarray,
              wd: jnp.ndarray) -> jnp.ndarray:
    from repro.models.partitioning import shard
    h = jax.nn.silu(x @ wg) * (x @ wu)
    h = shard(h, ("b",) + (None,) * (h.ndim - 2) + ("m",))
    return h @ wd
