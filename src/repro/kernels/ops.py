"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the kernels execute in interpret mode — the kernel body
runs in Python for correctness validation; on TPU they compile to Mosaic.
``interpret=None`` selects by backend, and any other backend is an error
rather than a silent interpret-mode run.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import flash_prefill as _fp
from repro.kernels import moe_dispatch as _moe
from repro.kernels import paged_attention as _pa
from repro.kernels import ssd_decode as _ssdd
from repro.kernels import ssd_scan as _ssd


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels compile for TPU and interpret "
                           f"on CPU; backend {backend!r} is neither")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("window", "blk", "interpret"))
def decode_attention(q, k_cache, v_cache, kv_pos, q_pos, *, window=None,
                     blk: int = 256, interpret: Optional[bool] = None):
    S = k_cache.shape[2]
    blk = min(blk, S)
    pad = (-S) % blk
    if pad:
        cfg = [(0, 0), (0, 0), (0, pad), (0, 0)]
        k_cache = jnp.pad(k_cache, cfg)
        v_cache = jnp.pad(v_cache, cfg)
        kv_pos = jnp.pad(kv_pos, [(0, 0), (0, pad)], constant_values=-1)
    return _da.decode_attention_kernel(q, k_cache, v_cache, kv_pos, q_pos,
                                       window=window, blk=blk,
                                       interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, q_pos,
                           layer=None, *, interpret: Optional[bool] = None):
    """Paged flash-decode (page table via scalar prefetch). Shapes are
    already page-aligned by construction, so no padding path is needed.
    ``layer`` reads one layer of a stacked [L,P,Hkv,psz,W] arena."""
    return _pa.paged_decode_attention_kernel(
        q, k_pages, v_pages, page_table, q_pos, layer,
        interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(q, k_pages, v_pages, page_table, q_start,
                           layer=None, *, interpret: Optional[bool] = None):
    """Multi-query paged verify attention for speculative decoding
    (DESIGN.md §8): C queries per sequence at positions q_start[b]+i over
    the paged KV arena. Shapes are page-aligned by construction.
    ``layer`` reads one layer of a stacked [L,P,Hkv,psz,W] arena."""
    return _pa.paged_verify_attention_kernel(
        q, k_pages, v_pages, page_table, q_start, layer,
        interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "window", "qblk",
                                             "kblk", "interpret"))
def flash_prefill(q, k, v, *, causal: bool = True, window=None,
                  qblk: int = 128, kblk: int = 128,
                  interpret: Optional[bool] = None):
    S = q.shape[1]
    qblk, kblk = min(qblk, S), min(kblk, S)
    assert S % qblk == 0 and S % kblk == 0, "pad sequence to block multiple"
    return _fp.flash_prefill_kernel(q, k, v, causal=causal, window=window,
                                    qblk=qblk, kblk=kblk,
                                    interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("causal", "window", "qblk",
                                             "kblk", "interpret"))
def flash_prefill_chunk(q, k, v, q_start, *, causal: bool = True, window=None,
                        qblk: int = 128, kblk: int = 128,
                        interpret: Optional[bool] = None):
    """Chunked prefill: q [B,C,Hq,hd] at positions q_start[b]+i over the
    full KV buffer k/v [B,S,Hkv,hd] (stale tail data beyond the chunk end is
    causally masked). One compilation per (C, S) shape pair serves every
    chunk offset — q_start is scalar-prefetched data, not shape."""
    C, S = q.shape[1], k.shape[1]
    qblk, kblk = min(qblk, C), min(kblk, S)
    assert C % qblk == 0 and S % kblk == 0, "pad chunk/buffer to block multiple"
    return _fp.flash_prefill_chunk_kernel(q, k, v, q_start, causal=causal,
                                          window=window, qblk=qblk, kblk=kblk,
                                          interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, b, c, d_skip, dt_bias, *, chunk: int = 64,
             interpret: Optional[bool] = None):
    T = x.shape[1]
    chunk = min(chunk, T)
    pad = (-T) % chunk
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)], constant_values=-1e9)
        b = jnp.pad(b, [(0, 0), (0, pad), (0, 0)])
        c = jnp.pad(c, [(0, 0), (0, pad), (0, 0)])
    y, h = _ssd.ssd_scan_kernel(x, dt, a_log, b, c, d_skip, dt_bias,
                                chunk=chunk,
                                interpret=_auto_interpret(interpret))
    return y[:, :T], h


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_decode_step(x, dt, a_log, b, c, d_skip, dt_bias, h, *,
                    interpret: Optional[bool] = None):
    """Single-token SSD recurrence: x [B,H,P], dt [B,H], b/c [B,N],
    h [B,H,P,N] -> (y [B,H,P], h' [B,H,P,N] f32). Identical contraction
    to ``models.ssm.ssd_step`` (the decode-side oracle)."""
    return _ssdd.ssd_decode_step_kernel(x, dt, a_log, b, c, d_skip, dt_bias,
                                        h, interpret=_auto_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_grouped_ffn(buf, wg, wu, wd, *, interpret: Optional[bool] = None):
    """Per-expert gated FFN over a dispatched [E,C,D] buffer -> [E,C,D].
    The dispatch/gather bracketing lives in ``models.moe`` — the kernel
    only does the three dense matmuls per expert."""
    return _moe.moe_grouped_ffn_kernel(buf, wg, wu, wd,
                                       interpret=_auto_interpret(interpret))
