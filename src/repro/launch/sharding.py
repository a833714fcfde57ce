"""Sharding rules: PartitionSpec pytrees for params, optimizer state, caches
and batches over the production meshes.

Serving: tensor-parallel over 'model' (d_ff / head-projection / expert axis),
weights replicated over 'data'/'pod'; batch over ('pod','data') when
divisible. KV caches are additionally sequence-parallel over 'model'
(flash-decode style partial-softmax sharding) — batch-only sharding leaves
e.g. internvl2-26b's decode_32k cache at 51.5 GB/device, far over v5e HBM.

Training: additionally FSDP-shards the non-'model' weight dim over 'data'
so AdamW state fits HBM for the largest configs.

jit INPUT shardings require exact divisibility (unlike internal
with_sharding_constraint, which GSPMD pads), so every rule here guards on
divisibility and falls back to the next-best dimension — e.g. an odd vocab
(122753) shards the d_model dim of the embedding instead, and granite's 40
experts fall back from expert-parallel to tensor-parallel inside each expert.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.launch.mesh import batch_axes, batch_ways


def _div(n: int, ways: int) -> bool:
    return ways > 0 and n % ways == 0


def param_specs(cfg: ArchConfig, mesh, train: bool) -> Dict[str, Any]:
    """PartitionSpec pytree matching init_params' structure."""
    mways = mesh.shape["model"]
    dways = mesh.shape["data"]
    fsdp = "data" if train else None

    def fs(dim: int):
        return fsdp if (fsdp and _div(dim, dways)) else None

    def ms(dim: int):
        return "model" if _div(dim, mways) else None

    def mat(d_in: int, d_out: int):
        """[*, d_in, d_out] weight: prefer model on d_out, FSDP on d_in;
        if d_out is not divisible, swap."""
        if _div(d_out, mways):
            return fs(d_in), "model"
        if _div(d_in, mways):
            return "model", fs(d_out)
        return fs(d_in), None

    D, F, V = cfg.d_model, cfg.d_ff, cfg.padded_vocab
    blk: Dict[str, Any] = {"ln1": P(None, None)}
    if cfg.has_attention:
        iq, oq = mat(D, cfg.q_dim)
        ik, ok_ = mat(D, cfg.kv_dim)
        blk.update(
            wq=P(None, iq, oq), wk=P(None, ik, ok_), wv=P(None, ik, ok_),
            wo=P(None, oq if oq == "model" else ms(cfg.q_dim), fs(D)),
        )
    if cfg.has_ssm:
        from repro.models.ssm import SSMParams
        d_in_proj = 2 * cfg.ssm_inner + 2 * cfg.ssm_state + cfg.ssm_heads
        conv_dim = cfg.ssm_inner + 2 * cfg.ssm_state
        ii, oo = mat(D, d_in_proj)
        blk["ssm"] = SSMParams(
            in_proj=P(None, ii, oo),
            conv_w=P(None, ms(conv_dim), None),
            conv_b=P(None, ms(conv_dim)),
            a_log=P(None, None),
            d_skip=P(None, None),
            dt_bias=P(None, None),
            norm_w=P(None, ms(cfg.ssm_inner)),
            out_proj=P(None, ms(cfg.ssm_inner), fs(D)),
        )
    if cfg.block_kind == "moe":
        from repro.models.moe import MoEParams
        E = cfg.n_experts
        if _div(E, mways):  # expert-parallel
            blk["moe"] = MoEParams(
                router=P(None, fs(D), None),
                wg=P(None, "model", fs(D), None),
                wu=P(None, "model", fs(D), None),
                wd=P(None, "model", None, fs(D)),
            )
        else:               # tensor-parallel inside each expert
            blk["moe"] = MoEParams(
                router=P(None, fs(D), None),
                wg=P(None, None, fs(D), ms(F)),
                wu=P(None, None, fs(D), ms(F)),
                wd=P(None, None, ms(F), fs(D)),
            )
        blk["ln2"] = P(None, None)
    elif cfg.d_ff > 0:
        blk.update(
            wg=P(None, fs(D), ms(F)),
            wu=P(None, fs(D), ms(F)),
            wd=P(None, ms(F), fs(D)),
            ln2=P(None, None),
        )
    # embeddings: vocab over 'model' when divisible, else d_model
    if _div(V, mways):
        emb = P("model", fs(D))
        head = P(fs(D), "model")
    else:  # odd vocab: shard the d_model dim instead
        emb = P(fs(V), ms(D))
        head = P(ms(D), None)
    out: Dict[str, Any] = {
        "embed": emb,
        "final_norm": P(None),
        "blocks": blk,
    }
    if not cfg.tied_embeddings:
        out["lm_head"] = head
    return out


def page_specs(cfg: ArchConfig, mesh) -> Dict[str, Any]:
    """KV page arena [L, n_pages, Hkv, page_size, W] (DESIGN.md §9):
    per-device KV-head slabs over 'model' when the head count divides the
    axis, replicated otherwise — the same jit-input divisibility rule as
    param_specs. Page tables stay replicated host data either way: paging
    is pure indirection, so one table addresses every device's slab."""
    mways = mesh.shape["model"]
    h = "model" if _div(cfg.n_kv_heads, mways) else None
    spec = P(None, None, h, None, None)
    return {"k_pages": spec, "v_pages": spec}


def cache_specs(cfg: ArchConfig, mesh, batch: int,
                buf_len: Optional[int] = None) -> Dict[str, Any]:
    mways = mesh.shape["model"]
    b_ax = batch_axes(mesh)
    bshard = b_ax if _div(batch, batch_ways(mesh)) else None
    c: Dict[str, Any] = {"length": P(bshard)}
    if cfg.has_attention:
        # sequence-parallel KV over 'model' (buf length always a multiple of
        # 16 for our shapes; guard anyway)
        sshard = "model" if (buf_len is None or _div(buf_len, mways)) else None
        c["k"] = P(None, bshard, None, sshard, None)
        c["v"] = P(None, bshard, None, sshard, None)
        c["kv_pos"] = P(bshard, sshard)
    if cfg.has_ssm:
        hshard = "model" if _div(cfg.ssm_heads, mways) else None
        conv_dim = cfg.ssm_inner + 2 * cfg.ssm_state
        c["ssm"] = P(None, bshard, hshard, None, None)
        c["conv"] = P(None, bshard,
                      "model" if _div(conv_dim, mways) else None, None)
    return c


def batch_spec(mesh, global_batch: int, ndim: int):
    """[B, ...] activations: batch over ('pod','data') when divisible."""
    b_ax = batch_axes(mesh)
    bshard = b_ax if _div(global_batch, batch_ways(mesh)) else None
    return P(bshard, *([None] * (ndim - 1)))


def to_shardings(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_specs(param_spec_tree):
    """AdamWState(step, mu, nu): moments shard like params."""
    from repro.training.optimizer import AdamWState
    return AdamWState(step=P(), mu=param_spec_tree,
                      nu=jax.tree.map(lambda s: s, param_spec_tree,
                                      is_leaf=lambda x: isinstance(x, P)))
