"""Unified configurable decoder/encoder: dense GQA, MoE, Mamba2-SSD, hybrid
(parallel attention+SSM), encoder-only — selected by ``ArchConfig``.

Layers are stacked [L, ...] and driven by ``jax.lax.scan`` so HLO size and
compile time are O(1) in depth (essential for the 48-layer dry-runs).

Three entry points per architecture:
  forward(params, inputs)                 -> logits        (train / encode)
  prefill(params, inputs, cache_len)      -> (last_logits, cache)
  decode_step(params, cache, tokens, act) -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models.partitioning import shard

Params = Dict[str, Any]
Cache = Dict[str, Any]


# ------------------------------------------------------------------ init

def init_params(cfg: ArchConfig, key: jax.Array, dtype=jnp.float32) -> Params:
    ks = iter(jax.random.split(key, 24))
    D, Lh = cfg.d_model, cfg.n_layers
    s = D ** -0.5
    p: Params = {
        "embed": (jax.random.normal(next(ks), (cfg.padded_vocab, D)) * s).astype(dtype),
        "final_norm": jnp.zeros((D,), dtype),
    }
    if not cfg.tied_embeddings:
        p["lm_head"] = (jax.random.normal(next(ks), (D, cfg.padded_vocab)) * s).astype(dtype)
    blk: Params = {"ln1": jnp.zeros((Lh, D), dtype)}
    if cfg.has_attention:
        blk["wq"] = (jax.random.normal(next(ks), (Lh, D, cfg.q_dim)) * s).astype(dtype)
        blk["wk"] = (jax.random.normal(next(ks), (Lh, D, cfg.kv_dim)) * s).astype(dtype)
        blk["wv"] = (jax.random.normal(next(ks), (Lh, D, cfg.kv_dim)) * s).astype(dtype)
        blk["wo"] = (jax.random.normal(next(ks), (Lh, cfg.q_dim, D)) * cfg.q_dim ** -0.5).astype(dtype)
    if cfg.has_ssm:
        sub = jax.random.split(next(ks), Lh)
        blk["ssm"] = jax.vmap(
            lambda k: SSM.init_ssm_params(k, D, cfg.ssm_inner, cfg.ssm_state,
                                          cfg.ssm_head_dim, cfg.ssm_conv, dtype)
        )(sub)
    if cfg.block_kind == "moe":
        sub = jax.random.split(next(ks), Lh)
        blk["moe"] = jax.vmap(
            lambda k: MOE.init_moe_params(k, D, cfg.d_ff, cfg.n_experts, dtype)
        )(sub)
        blk["ln2"] = jnp.zeros((Lh, D), dtype)
    elif cfg.d_ff > 0:
        f = cfg.d_ff
        blk["wg"] = (jax.random.normal(next(ks), (Lh, D, f)) * s).astype(dtype)
        blk["wu"] = (jax.random.normal(next(ks), (Lh, D, f)) * s).astype(dtype)
        blk["wd"] = (jax.random.normal(next(ks), (Lh, f, D)) * f ** -0.5).astype(dtype)
        blk["ln2"] = jnp.zeros((Lh, D), dtype)
    p["blocks"] = blk
    return p


def init_cache(cfg: ArchConfig, batch: int, buf_len: int,
               dtype=jnp.float32) -> Cache:
    """Empty decode cache. buf_len: KV slots (ring size if sliding window)."""
    c: Cache = {"length": jnp.zeros((batch,), jnp.int32)}
    if cfg.has_attention:
        c["k"] = jnp.zeros((cfg.n_layers, batch, cfg.n_kv_heads, buf_len,
                            cfg.head_dim), dtype)
        c["v"] = jnp.zeros_like(c["k"])
        c["kv_pos"] = jnp.full((batch, buf_len), -1, jnp.int32)
    if cfg.has_ssm:
        c["ssm"] = jnp.zeros((cfg.n_layers, batch, cfg.ssm_heads,
                              cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
        c["conv"] = jnp.zeros((cfg.n_layers, batch,
                               cfg.ssm_inner + 2 * cfg.ssm_state,
                               cfg.ssm_conv - 1), dtype)
    return c


# ------------------------------------------------------------------ blocks

def _attn_seq(cfg: ArchConfig, bp: Params, h: jnp.ndarray,
              positions: jnp.ndarray, attn_impl: str,
              window: Optional[int]) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Full-sequence attention. h: [B,S,D] (already normed). Returns
    (out [B,S,D], kv dict for cache building)."""
    B, S, D = h.shape
    q = (h @ bp["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (h @ bp["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = (h @ bp["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = shard(L.apply_rope(q, positions, cfg.rope_theta), ("b", None, "m", None))
    k = shard(L.apply_rope(k, positions, cfg.rope_theta), ("b", None, "m", None))
    v = shard(v, ("b", None, "m", None))
    if attn_impl == "dense":
        mask = L.band_mask(positions, positions, cfg.causal, window)
        out = L.attention(q, k, v, mask)
    else:
        out = L.chunked_attention(q, k, v, positions, positions,
                                  causal=cfg.causal, window=window)
    out = shard(out, ("b", None, "m", None))
    return out.reshape(B, S, cfg.q_dim) @ bp["wo"], {"k": k, "v": v}


def _ffn(cfg: ArchConfig, bp: Params, x: jnp.ndarray,
         moe_impl: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Post-mixer FFN (residual applied by caller). Returns (out, aux_loss)."""
    zero = jnp.zeros((), jnp.float32)
    if cfg.block_kind == "moe":
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        mp = MOE.MoEParams(bp["moe"].router, bp["moe"].wg,
                           bp["moe"].wu, bp["moe"].wd)
        grouped = moe_impl in ("grouped", "grouped_kernel")
        if grouped and h.ndim == 3 and h.shape[1] > 1:
            y, aux = MOE.moe_ffn_grouped(mp, h, cfg.top_k)
        elif grouped:
            # decode (one token per row): lossless single-group dispatch —
            # each expert only sees its routed rows instead of the dense
            # oracle's all-experts-every-token sweep (DESIGN.md §12)
            y, aux = MOE.moe_ffn_grouped_decode(
                mp, h, cfg.top_k, use_kernel=moe_impl == "grouped_kernel")
        elif moe_impl == "dense":
            y, aux = MOE.moe_ffn_dense(mp, h, cfg.top_k)
        else:
            y, aux = MOE.moe_ffn(mp, h, cfg.top_k)
        return y, aux
    if cfg.d_ff > 0:
        h = L.rms_norm(x, bp["ln2"], cfg.norm_eps)
        return L.gated_mlp(h, bp["wg"], bp["wu"], bp["wd"]), zero
    return jnp.zeros_like(x), zero


def _block_seq(cfg: ArchConfig, bp: Params, x: jnp.ndarray,
               positions: jnp.ndarray, attn_impl: str, window: Optional[int],
               want_cache: bool, moe_impl: str, use_ssd_kernel: bool = False):
    """One layer over a full sequence. Returns (x, layer_cache|{}, aux)."""
    h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
    cache: Dict[str, Any] = {}
    parts = []
    if cfg.has_attention:
        a_out, kv = _attn_seq(cfg, bp, h, positions, attn_impl, window)
        parts.append(a_out)
        if want_cache:
            cache.update(kv)
    if cfg.has_ssm:
        if want_cache:
            s_out, hS, cS = SSM.ssm_mixer_with_state(
                SSM.SSMParams(*[bp["ssm"][i] for i in range(len(bp["ssm"]))]),
                h, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_head_dim,
                use_kernel=use_ssd_kernel)
            cache["ssm"], cache["conv"] = hS, cS
        else:
            s_out = SSM.ssm_mixer(
                SSM.SSMParams(*[bp["ssm"][i] for i in range(len(bp["ssm"]))]),
                h, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_head_dim,
                use_kernel=use_ssd_kernel)
        parts.append(s_out)
    mixer = parts[0] if len(parts) == 1 else 0.5 * (parts[0] + parts[1])
    x = x + mixer
    f_out, aux = _ffn(cfg, bp, x, moe_impl)
    return x + f_out, cache, aux


# ------------------------------------------------------------------ forward

@dataclasses.dataclass(frozen=True)
class ModelOptions:
    attn_impl: str = "auto"       # auto | dense | chunked
    moe_impl: str = "grouped"     # grouped | sorted | dense
    remat: bool = False
    use_ssd_kernel: bool = False
    train_window: Optional[int] = None  # cap attention window in training
    unroll: bool = False  # unroll the layer scan (dry-run cost analysis:
                          # XLA counts while-loop bodies once, so scan-based
                          # lowerings under-report FLOPs by ~n_layers)

    def resolve_attn(self, seq_len: int) -> str:
        if self.attn_impl != "auto":
            return self.attn_impl
        return "chunked" if seq_len > 2048 else "dense"


def embed_inputs(cfg: ArchConfig, params: Params, inputs: jnp.ndarray) -> jnp.ndarray:
    """Token ids [B,S] -> embeddings; embedding-input archs pass [B,S,D]."""
    if inputs.ndim == 3:
        return inputs
    return params["embed"][inputs]


def unembed(cfg: ArchConfig, params: Params, x: jnp.ndarray,
            keep_padded: bool = False) -> jnp.ndarray:
    """Project to (padded) vocab. keep_padded=True returns [., padded_vocab]
    with pad lanes masked to -inf (loss path: keeps the logits vocab-sharded,
    no all-reduce); otherwise slices back to vocab_size for the API."""
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    logits = x @ head
    Vp, V = cfg.padded_vocab, cfg.vocab_size
    if not keep_padded:
        return logits[..., :V] if Vp != V else logits
    if Vp != V:
        # broadcast-add bias (fuses into the matmul epilogue) — a where()
        # over the logits materializes extra full-logits f32 copies
        # (measured +30% on yi-6b's train memory term).
        bias = jnp.where(jnp.arange(Vp) >= V, -1e30, 0.0).astype(logits.dtype)
        logits = logits + bias
    return logits


def forward(cfg: ArchConfig, params: Params, inputs: jnp.ndarray,
            opts: ModelOptions = ModelOptions(),
            keep_padded: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward (training / encoder). Returns (logits, aux)."""
    x = shard(embed_inputs(cfg, params, inputs), ("b", None, None))
    B, S, D = x.shape
    positions = jnp.arange(S)
    impl = opts.resolve_attn(S)
    window = opts.train_window or (
        cfg.sliding_window if (cfg.sliding_window and cfg.sliding_window < S) else None)

    def body(carry, bp):
        x, aux = carry
        x, _, a = _block_seq(cfg, bp, x, positions, impl, window, False,
                             opts.moe_impl, opts.use_ssd_kernel)
        return (x, aux + a), None

    f = jax.checkpoint(body) if opts.remat else body
    (x, aux), _ = jax.lax.scan(f, (x, jnp.zeros((), jnp.float32)),
                               params["blocks"], unroll=opts.unroll)
    return unembed(cfg, params, x, keep_padded=keep_padded), aux / cfg.n_layers


def loss_fn(cfg: ArchConfig, params: Params, inputs: jnp.ndarray,
            labels: jnp.ndarray, opts: ModelOptions = ModelOptions(),
            aux_weight: float = 0.01) -> jnp.ndarray:
    """Next-token (decoder) or per-frame (encoder) cross-entropy."""
    logits, aux = forward(cfg, params, inputs, opts, keep_padded=True)
    if cfg.causal:
        logits = logits[:, :-1]
        targets = labels[:, 1:]
    else:
        targets = labels
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logits.astype(jnp.float32), targets[..., None], axis=-1)[..., 0]
    valid = (targets >= 0).astype(jnp.float32)
    ce = jnp.sum((lse - picked) * valid) / jnp.maximum(valid.sum(), 1.0)
    return ce + aux_weight * aux


# ------------------------------------------------------------------ prefill

def prefill(cfg: ArchConfig, params: Params, inputs: jnp.ndarray,
            buf_len: int, opts: ModelOptions = ModelOptions()
            ) -> Tuple[jnp.ndarray, Cache]:
    """Process prompts (all rows full length S). Returns (last_logits, cache).

    buf_len >= S for full-attention archs; for sliding-window long-context,
    buf_len = window and only the last ``window`` tokens are cached (ring).
    """
    assert cfg.causal, "encoder-only archs have no prefill/decode"
    x = embed_inputs(cfg, params, inputs)
    B, S, D = x.shape
    positions = jnp.arange(S)
    impl = opts.resolve_attn(S)
    window = cfg.sliding_window if (cfg.sliding_window and buf_len < S) else None
    if window is not None:
        assert buf_len == window, (buf_len, window)

    def body(x, bp):
        x, cache, _ = _block_seq(cfg, bp, x, positions, impl, window, True,
                                 opts.moe_impl, opts.use_ssd_kernel)
        return x, cache

    x, caches = jax.lax.scan(body, x, params["blocks"], unroll=opts.unroll)
    out: Cache = {"length": jnp.full((B,), S, jnp.int32)}
    if cfg.has_attention:
        k, v = caches["k"], caches["v"]            # [L,B,S,Hkv,hd]
        k = k.swapaxes(2, 3)                       # [L,B,Hkv,S,hd]
        v = v.swapaxes(2, 3)
        if buf_len >= S:
            pad = ((0, 0), (0, 0), (0, 0), (0, buf_len - S), (0, 0))
            out["k"], out["v"] = jnp.pad(k, pad), jnp.pad(v, pad)
            kv_pos = jnp.where(jnp.arange(buf_len) < S, jnp.arange(buf_len), -1)
        else:  # ring: keep last buf_len tokens at slot p % buf_len
            tail_pos = jnp.arange(S - buf_len, S)
            slots = tail_pos % buf_len
            kt, vt = k[..., -buf_len:, :], v[..., -buf_len:, :]
            out["k"] = jnp.zeros_like(kt).at[..., slots, :].set(kt)
            out["v"] = jnp.zeros_like(vt).at[..., slots, :].set(vt)
            kv_pos = jnp.zeros((buf_len,), jnp.int32).at[slots].set(tail_pos)
        out["kv_pos"] = jnp.broadcast_to(kv_pos, (B, buf_len))
    if cfg.has_ssm:
        out["ssm"], out["conv"] = caches["ssm"], caches["conv"]
    return unembed(cfg, params, x[:, -1]), out


# ---------------------------------------------------------- chunked prefill

def prefill_chunk(cfg: ArchConfig, params: Params, cache: Cache,
                  tokens: jnp.ndarray, opts: ModelOptions = ModelOptions(),
                  use_kernel: bool = False) -> Tuple[jnp.ndarray, Cache]:
    """Process ONE prompt chunk against a slot-style cache (DESIGN.md §5).

    tokens: [B,C] — the next C prompt tokens of every row, appended at each
    row's current ``cache['length']``. The chunk's KV is written into the
    buffer and its queries attend over everything cached so far (kv_pos
    masking, or the Pallas chunk kernel with ``use_kernel=True`` — safe
    because the buffer is append-only, so positions beyond the chunk end are
    causally masked). Returns (logits of the chunk's last position [B,V],
    new cache). Caller guarantees length+C <= buf_len (no ring wrap).

    SSM and hybrid blocks thread the recurrent state through the chunked
    dual form (DESIGN.md §12): each chunk consumes the cache's carried
    ``ssm``/``conv`` state and emits the post-chunk state, so chaining
    chunks is exactly identical to monolithic ``prefill`` (the equivalence
    oracle in tests/test_kernels.py pins it). Exact logit-equivalence
    holds for dense-FFN blocks (MoE capacity is sequence-length dependent).
    """
    assert cfg.causal and (cfg.has_attention or cfg.has_ssm)
    B, C = tokens.shape
    x = params["embed"][tokens]                    # [B,C,D]
    length = cache["length"]                       # [B]
    q_pos = length[:, None] + jnp.arange(C, dtype=length.dtype)  # [B,C]
    new_cache: Cache = {"length": length + C}
    barr = jnp.arange(B)[:, None]
    window = None
    new_kv_pos = None
    if cfg.has_attention:
        buf_len = cache["k"].shape[3]
        if cfg.sliding_window and buf_len <= cfg.sliding_window:
            window = cfg.sliding_window
        slot = q_pos                               # append-only: no ring wrap
        new_kv_pos = cache["kv_pos"].at[barr, slot].set(q_pos)
        new_cache["kv_pos"] = new_kv_pos

    def body(x, xs):
        bp, lc = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        new_lc: Dict[str, Any] = {}
        parts = []
        if cfg.has_attention:
            q = (h @ bp["wq"]).reshape(B, C, cfg.n_heads, cfg.head_dim)
            k = (h @ bp["wk"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ bp["wv"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
            q = shard(L.apply_rope(q, q_pos, cfg.rope_theta),
                      ("b", None, "m", None))
            k = L.apply_rope(k, q_pos, cfg.rope_theta)
            kc = lc["k"].at[barr, :, slot].set(k)  # [B,Hkv,buf,hd]
            vc = lc["v"].at[barr, :, slot].set(v)
            if use_kernel:
                from repro.kernels import ops as _kops
                a = _kops.flash_prefill_chunk(q, kc.swapaxes(1, 2),
                                              vc.swapaxes(1, 2), length,
                                              window=window)
            else:
                a = L.chunk_decode_attention(q, kc, vc, new_kv_pos, q_pos,
                                             window)
            parts.append(a.reshape(B, C, cfg.q_dim) @ bp["wo"])
            new_lc["k"], new_lc["v"] = kc, vc
        if cfg.has_ssm:
            sp = SSM.SSMParams(*[bp["ssm"][i] for i in range(len(bp["ssm"]))])
            s_out, hS, cS = SSM.ssm_mixer_with_state(
                sp, h, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_head_dim,
                use_kernel=use_kernel and not cfg.has_attention,
                h0=lc["ssm"], conv0=lc["conv"])
            parts.append(s_out)
            new_lc["ssm"] = hS
            new_lc["conv"] = cS.astype(lc["conv"].dtype)
        mixer = parts[0] if len(parts) == 1 else 0.5 * (parts[0] + parts[1])
        x = x + mixer
        f_out, _ = _ffn(cfg, bp, x, opts.moe_impl)
        return x + f_out, new_lc

    layer_caches = {k: cache[k] for k in ("k", "v", "ssm", "conv")
                    if k in cache}
    x, new_layer_caches = jax.lax.scan(body, x, (params["blocks"], layer_caches),
                                       unroll=opts.unroll)
    new_cache.update(new_layer_caches)
    return unembed(cfg, params, x[:, -1]), new_cache


def _write_tokens(arena: jnp.ndarray, layer, page_table: jnp.ndarray,
                 start: jnp.ndarray, new: jnp.ndarray,
                 valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Write row b's run of C new K or V rows, ``new[b]`` [C,Hkv,hd], at
    logical positions start[b]..start[b]+C-1 of layer ``layer`` of the
    stacked arena [L,P,Hkv,psz,W] (rows with ``valid[b]`` false write
    nothing; lanes hd..W-1 are written as zeros). Returns the updated arena.

    Whole pages move: each page a run touches is gathered, the run's rows
    are merged in, and the page is scattered back to where it came from.
    A page-shaped update keeps the arena in its own row-major layout, the
    one the paged kernel reads, so inside a compiled step whose arena is
    donated the write is in place; a scatter of single token rows would
    make the TPU compiler relayout the whole arena around it. Pages the
    run does not reach and untabled pages are dropped, so no two updates
    of one scatter name the same page."""
    B, C = new.shape[:2]
    n_pages, _, psz = arena.shape[1:4]
    maxp = page_table.shape[1]
    touch = (C + 2 * psz - 2) // psz               # pages a run can span
    lp = start[:, None] // psz + jnp.arange(touch)[None, :]        # [B,T]
    phys = page_table[jnp.arange(B)[:, None], jnp.minimum(lp, maxp - 1)]
    idx = (lp[..., None] * psz + jnp.arange(psz)
           - start[:, None, None])                 # [B,T,psz] run index
    inrun = (idx >= 0) & (idx < C)
    ok = (lp < maxp) & (phys >= 0) & inrun.any(-1)
    if valid is not None:
        ok &= valid[:, None]
    cur = arena[layer, jnp.where(ok, phys, 0)]     # [B,T,Hkv,psz,W]
    rows = L.lane_pad(new[jnp.arange(B)[:, None, None],
                          jnp.clip(idx, 0, C - 1)], arena.shape[-1])
    upd = jnp.where(inrun[:, :, None, :, None],
                    rows.swapaxes(2, 3).astype(arena.dtype), cur)
    return arena.at[layer, jnp.where(ok, phys, n_pages)].set(upd,
                                                            mode="drop")


def prefill_chunk_paged(cfg: ArchConfig, params: Params, pages: Cache,
                        page_table: jnp.ndarray, lengths: jnp.ndarray,
                        tokens: jnp.ndarray,
                        opts: ModelOptions = ModelOptions(),
                        use_kernel: bool = False) -> Tuple[jnp.ndarray, Cache]:
    """Process ONE prompt chunk against the paged KV arena (DESIGN.md §5).

    tokens: [B,C] appended at logical positions ``lengths[b]+i``; the page
    table must already cover lengths+C tokens (the pool extends BEFORE the
    chunk — incremental allocation, not a peak reservation). The chunk's KV
    is written into its pages of the arena, which every layer carries and
    returns whole (``_write_tokens``), then its queries attend over the
    gathered page view (kv-position masking, or the Pallas chunk kernel —
    untabled entries sit at logical positions beyond the chunk end, so
    causal masking covers them). Returns (logits of the chunk's last
    position [B,V], new pages). Lengths/page tables are host-side pool
    state — caller advances.
    """
    assert cfg.causal and cfg.has_attention and not cfg.has_ssm
    B, C = tokens.shape
    psz = pages["k_pages"].shape[3]
    x = params["embed"][tokens]                    # [B,C,D]
    q_pos = lengths[:, None] + jnp.arange(C, dtype=lengths.dtype)  # [B,C]

    def body(carry, xs):
        x, kp, vp = carry                          # kp/vp: whole arena
        bp, l = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        q = (h @ bp["wq"]).reshape(B, C, cfg.n_heads, cfg.head_dim)
        k = (h @ bp["wk"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ bp["wv"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        q = shard(L.apply_rope(q, q_pos, cfg.rope_theta), ("b", None, "m", None))
        k = L.apply_rope(k, q_pos, cfg.rope_theta)
        kp = _write_tokens(kp, l, page_table, lengths, k)
        vp = _write_tokens(vp, l, page_table, lengths, v)
        hd = cfg.head_dim
        kc = L.gather_pages(kp, page_table, l)[..., :hd]  # [B,Hkv,maxp*psz,hd]
        vc = L.gather_pages(vp, page_table, l)[..., :hd]
        if use_kernel:
            from repro.kernels import ops as _kops
            a = _kops.flash_prefill_chunk(q, kc.swapaxes(1, 2),
                                          vc.swapaxes(1, 2), lengths)
        else:
            kv_pos = L.paged_kv_positions(page_table, psz)
            a = L.chunk_decode_attention(q, kc, vc, kv_pos, q_pos)
        x = x + a.reshape(B, C, cfg.q_dim) @ bp["wo"]
        f_out, _ = _ffn(cfg, bp, x, opts.moe_impl)
        return (x + f_out, kp, vp), None

    (x, kp, vp), _ = jax.lax.scan(
        body, (x, pages["k_pages"], pages["v_pages"]),
        (params["blocks"], jnp.arange(cfg.n_layers)), unroll=opts.unroll)
    return unembed(cfg, params, x[:, -1]), {"k_pages": kp, "v_pages": vp}


# ------------------------------------------------------- speculative verify

def verify_step_paged(cfg: ArchConfig, params: Params, pages: Cache,
                      page_table: jnp.ndarray, lengths: jnp.ndarray,
                      tokens: jnp.ndarray,
                      opts: ModelOptions = ModelOptions(),
                      use_kernel: bool = False) -> Tuple[jnp.ndarray, Cache]:
    """Multi-token draft-verify step over the paged KV arena (DESIGN.md §8).

    tokens: [B,C] — the last committed token followed by C-1 draft tokens,
    appended at logical positions ``lengths[b]+i``; the page table must
    already cover lengths+C tokens (the pool extends BEFORE the step; the
    caller rolls back pages for rejected drafts with pool.truncate after
    acceptance). The window's KV is written into its pages, then every
    query attends over the gathered page view with the causal staircase
    (query i sees positions 0..lengths[b]+i) — or the Pallas
    ``paged_verify_attention`` kernel with ``use_kernel=True``.

    Returns (logits [B,C,V], new pages): logits[:, i] is the target model's
    next-token distribution AFTER consuming token i of the window — the
    acceptance test compares argmax(logits[:, i]) against draft i+1
    (greedy equivalence). This is ``prefill_chunk_paged`` generalized to
    return every position's logits instead of only the last — pad rows
    (page_table all -1) scatter nothing and produce garbage logits the
    caller ignores, exactly like inactive decode rows.
    """
    assert cfg.causal and cfg.has_attention and not cfg.has_ssm
    B, C = tokens.shape
    x = params["embed"][tokens]                    # [B,C,D]
    q_pos = lengths[:, None] + jnp.arange(C, dtype=lengths.dtype)  # [B,C]

    def body(carry, xs):
        x, kp, vp = carry                          # kp/vp: whole arena
        bp, l = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        q = (h @ bp["wq"]).reshape(B, C, cfg.n_heads, cfg.head_dim)
        k = (h @ bp["wk"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ bp["wv"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        q = shard(L.apply_rope(q, q_pos, cfg.rope_theta), ("b", None, "m", None))
        k = L.apply_rope(k, q_pos, cfg.rope_theta)
        kp = _write_tokens(kp, l, page_table, lengths, k)
        vp = _write_tokens(vp, l, page_table, lengths, v)
        if use_kernel:
            from repro.kernels import ops as _kops
            a = _kops.paged_verify_attention(q, kp, vp, page_table, lengths, l)
        else:
            a = L.paged_verify_attention(q, kp, vp, page_table, lengths, l)
        x = x + a.reshape(B, C, cfg.q_dim) @ bp["wo"]
        f_out, _ = _ffn(cfg, bp, x, "dense" if cfg.block_kind != "moe"
                        else opts.moe_impl)
        return (x + f_out, kp, vp), None

    (x, kp, vp), _ = jax.lax.scan(
        body, (x, pages["k_pages"], pages["v_pages"]),
        (params["blocks"], jnp.arange(cfg.n_layers)), unroll=opts.unroll)
    return unembed(cfg, params, x), {"k_pages": kp, "v_pages": vp}


# ------------------------------------------------------------------ decode

def decode_step(cfg: ArchConfig, params: Params, cache: Cache,
                tokens: jnp.ndarray, active: Optional[jnp.ndarray] = None,
                opts: ModelOptions = ModelOptions()
                ) -> Tuple[jnp.ndarray, Cache]:
    """One decode iteration for every (active) slot.

    tokens: [B] int32; active: [B] bool (inactive slots keep their state —
    this is the decode-mask-matrix column from SLICE's rate allocator).
    Returns (logits [B,V], new cache).
    """
    assert cfg.causal
    B = tokens.shape[0]
    if active is None:
        active = jnp.ones((B,), bool)
    x = params["embed"][tokens]                    # [B,D]
    length = cache["length"]                       # [B]
    q_pos = length
    new_cache: Cache = {"length": jnp.where(active, length + 1, length)}
    buf_len = cache["k"].shape[3] if cfg.has_attention else 0
    window = None
    if cfg.has_attention and cfg.sliding_window and buf_len <= cfg.sliding_window:
        window = cfg.sliding_window
    slot = (q_pos % buf_len) if buf_len else q_pos
    if cfg.has_attention:
        kv_pos = cache["kv_pos"]
        new_kv_pos = kv_pos.at[jnp.arange(B), slot].set(
            jnp.where(active, q_pos, kv_pos[jnp.arange(B), slot]))
        new_cache["kv_pos"] = new_kv_pos

    def body(x, xs):
        bp, layer_cache = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        new_lc: Dict[str, Any] = {}
        parts = []
        if cfg.has_attention:
            q = (h @ bp["wq"]).reshape(B, cfg.n_heads, cfg.head_dim)
            k = (h @ bp["wk"]).reshape(B, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ bp["wv"]).reshape(B, cfg.n_kv_heads, cfg.head_dim)
            q = shard(L.apply_rope(q[:, None], q_pos[:, None],
                                   cfg.rope_theta)[:, 0], ("b", "m", None))
            k = L.apply_rope(k[:, None], q_pos[:, None], cfg.rope_theta)[:, 0]
            kc, vc = layer_cache["k"], layer_cache["v"]
            sel = active[:, None, None]
            kc = kc.at[jnp.arange(B), :, slot].set(
                jnp.where(sel, k, kc[jnp.arange(B), :, slot]))
            vc = vc.at[jnp.arange(B), :, slot].set(
                jnp.where(sel, v, vc[jnp.arange(B), :, slot]))
            a = L.decode_attention(q, kc, vc, new_kv_pos, q_pos, window)
            parts.append(a.reshape(B, cfg.q_dim) @ bp["wo"])
            new_lc["k"], new_lc["v"] = kc, vc
        if cfg.has_ssm:
            sp = SSM.SSMParams(*[bp["ssm"][i] for i in range(len(bp["ssm"]))])
            s_out, hS, cS = SSM.ssm_mixer_step(
                sp, h, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_head_dim,
                layer_cache["ssm"], layer_cache["conv"])
            sel2 = active[:, None, None]
            hS = jnp.where(active[:, None, None, None], hS, layer_cache["ssm"])
            cS = jnp.where(sel2, cS, layer_cache["conv"])
            parts.append(s_out)
            new_lc["ssm"], new_lc["conv"] = hS, cS
        mixer = parts[0] if len(parts) == 1 else 0.5 * (parts[0] + parts[1])
        x = x + mixer
        f_out, _ = _ffn(cfg, bp, x, "dense" if cfg.block_kind != "moe"
                        else opts.moe_impl)
        return x + f_out, new_lc

    layer_caches = {k: cache[k] for k in ("k", "v", "ssm", "conv") if k in cache}
    x, new_layer_caches = jax.lax.scan(body, x, (params["blocks"], layer_caches),
                                       unroll=opts.unroll)
    new_cache.update(new_layer_caches)
    logits = unembed(cfg, params, x)
    return logits, new_cache


# ------------------------------------------------------------ paged decode

ARENA_LANES = 128           # a TPU vector register's lane count


def init_paged_cache(cfg: ArchConfig, n_pages: int, page_size: int,
                     dtype=jnp.float32) -> Cache:
    """Shared KV page arena: k/v_pages [L, n_pages, Hkv, page_size, W],
    W = hd rounded up to whole 128-lane rows (``ARENA_LANES``; lanes hd..W-1
    stay zero). With a lane-aligned minor dim the TPU's default layout for
    the arena is row-major, a page's (page_size, W) block contiguous — the
    layout the paged kernel reads — so the compiled steps take, update and
    return the arena in place with no relayout (DESIGN.md §3, arena
    ownership); at hd 64 the TPU's default layout would put the pages
    dimension minor instead. Page ownership lives in
    serving.kv_pool.KVPagePool; sequences address the arena through
    per-step [B, max_pages] page tables (decode_step_paged).

    Attention-free (pure SSM) archs get a zero-width arena (Hkv = hd = 0):
    the page table stays the logical token-length ledger for every arch
    (DESIGN.md §12) but the pages carry no bytes — their recurrent state
    lives in the constant-size arena from ``init_state_arena``."""
    assert cfg.has_attention or cfg.has_ssm, "arch has no decode cache"
    hkv = cfg.n_kv_heads if cfg.has_attention else 0
    hd = cfg.head_dim if cfg.has_attention else 0
    width = -(-hd // ARENA_LANES) * ARENA_LANES
    shape = (cfg.n_layers, n_pages, hkv, page_size, width)
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


def init_state_arena(cfg: ArchConfig, n_slots: int,
                     dtype=jnp.float32) -> Cache:
    """Constant-size recurrent-state arena (DESIGN.md §12): per layer one
    [H, P, N] SSD state (f32 — the recurrence accumulates in f32) and one
    [C, K-1] conv tail per slot. Slot ownership lives in
    serving.state_store.SSMStateStore; the whole per-task state is a single
    fixed-size "page", so suspend/resume snapshots one slot slice."""
    assert cfg.has_ssm, "state arena needs SSM layers"
    return {
        "ssm_state": jnp.zeros((cfg.n_layers, n_slots, cfg.ssm_heads,
                                cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        "conv_state": jnp.zeros((cfg.n_layers, n_slots,
                                 cfg.ssm_inner + 2 * cfg.ssm_state,
                                 cfg.ssm_conv - 1), dtype),
    }


def decode_step_paged(cfg: ArchConfig, params: Params, pages: Cache,
                      page_table: jnp.ndarray, lengths: jnp.ndarray,
                      tokens: jnp.ndarray, active: Optional[jnp.ndarray] = None,
                      opts: ModelOptions = ModelOptions(),
                      use_kernel: bool = False,
                      state_slots: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Cache]:
    """One decode iteration over the paged KV arena (DESIGN.md §3
    adaptation #2) with per-layer cache-kind dispatch (§12): attention
    layers read/write the paged KV arena, SSM layers the constant-size
    state arena, and hybrid blocks mix both kinds in the same step.

    pages: init_paged_cache dict (plus the init_state_arena entries for
    SSM/hybrid archs); page_table: [B, maxp] physical page per logical
    page (-1 unused; row b must cover lengths[b]+1 tokens — the pool
    extends BEFORE the step); lengths: [B] cached tokens per row (the new
    token is written at logical position lengths[b]); tokens: [B] int32;
    active: [B] bool — inactive rows write nothing and their logits are
    garbage to be ignored;
    state_slots: [B] state-arena slot per row (-1 pad rows), required for
    SSM/hybrid archs.

    Every arena rides the layer scan's carry whole: layer l writes and
    reads its part in place, so a caller that donates ``pages`` gets the
    same buffers back (DESIGN.md §3, arena ownership).

    Returns (logits [B,V], new pages). Lengths/page tables/slots are
    host-side pool state, not device state — the caller advances them.
    """
    assert cfg.causal and (cfg.has_attention or cfg.has_ssm)
    assert not cfg.has_ssm or state_slots is not None
    B = tokens.shape[0]
    if active is None:
        active = jnp.ones((B,), bool)
    x = params["embed"][tokens]                    # [B,D]
    q_pos = lengths
    if cfg.has_ssm:
        n_slots = pages["ssm_state"].shape[1]
        slot_rd = jnp.clip(state_slots, 0, n_slots - 1)  # pad rows read slot 0
        slot_wr = jnp.where(active & (state_slots >= 0), state_slots, n_slots)

    def body(carry, xs):
        x, arena = carry                           # the whole arena dict
        bp, l = xs
        arena = dict(arena)
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        parts = []
        if cfg.has_attention:
            q = (h @ bp["wq"]).reshape(B, cfg.n_heads, cfg.head_dim)
            k = (h @ bp["wk"]).reshape(B, cfg.n_kv_heads, cfg.head_dim)
            v = (h @ bp["wv"]).reshape(B, cfg.n_kv_heads, cfg.head_dim)
            q = shard(L.apply_rope(q[:, None], q_pos[:, None],
                                   cfg.rope_theta)[:, 0], ("b", "m", None))
            k = L.apply_rope(k[:, None], q_pos[:, None], cfg.rope_theta)[:, 0]
            # inactive and untabled rows write nothing
            kp = _write_tokens(arena["k_pages"], l, page_table, q_pos,
                               k[:, None], active)
            vp = _write_tokens(arena["v_pages"], l, page_table, q_pos,
                               v[:, None], active)
            if use_kernel:
                from repro.kernels import ops as _kops
                a = _kops.paged_decode_attention(q, kp, vp, page_table, q_pos,
                                                 l)
            else:
                a = L.paged_decode_attention(q, kp, vp, page_table, q_pos, l)
            parts.append(a.reshape(B, cfg.q_dim) @ bp["wo"])
            arena["k_pages"], arena["v_pages"] = kp, vp
        # attention-free archs carry their zero-width k/v arena untouched
        if cfg.has_ssm:
            sp = SSM.SSMParams(*[bp["ssm"][i] for i in range(len(bp["ssm"]))])
            hS = arena["ssm_state"][l, slot_rd]    # [B,H,P,N]
            cS = arena["conv_state"][l, slot_rd]   # [B,C,K-1]
            s_out, hS2, cS2 = SSM.ssm_mixer_step(
                sp, h, cfg.ssm_inner, cfg.ssm_state, cfg.ssm_head_dim,
                hS, cS)
            parts.append(s_out)
            # inactive/pad rows scatter out of bounds and are dropped
            arena["ssm_state"] = arena["ssm_state"].at[l, slot_wr].set(
                hS2, mode="drop")
            arena["conv_state"] = arena["conv_state"].at[l, slot_wr].set(
                cS2.astype(arena["conv_state"].dtype), mode="drop")
        mixer = parts[0] if len(parts) == 1 else 0.5 * (parts[0] + parts[1])
        x = x + mixer
        f_out, _ = _ffn(cfg, bp, x, "dense" if cfg.block_kind != "moe"
                        else opts.moe_impl)
        return (x + f_out, arena), None

    (x, new_pages), _ = jax.lax.scan(
        body, (x, dict(pages)),
        (params["blocks"], jnp.arange(cfg.n_layers)), unroll=opts.unroll)
    return unembed(cfg, params, x), new_pages
