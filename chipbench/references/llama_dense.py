"""Plain reference of a llama-style dense decoder, for the benchmark's check.

Pre-norm blocks: RMSNorm with a (1 + w) gain, grouped-query attention with
rotary positions (rotate-half, frequencies theta^(-2i/hd)) and a causal
softmax in float32, then RMSNorm and a SiLU-gated MLP; a final RMSNorm and an
untied (or tied) output head. Plain ``jax.numpy``, full sequences, no cache,
no kernels, nothing imported from the program under test.

``init_weights`` makes the weights the benchmark hands to both the program
and this reference, in the program's parameter layout, from a seed, in one
jitted call. ``gaps`` runs the forward pass in float32 at matmul precision
"highest" and returns, at each asked position, how far the reference's logit
of a given token lies below its best logit. ``argmax_low`` runs the same pass
in a lower precision, the controls: bfloat16 weights and activations, or
weight-only int8 with bfloat16 activations.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("ln1", "ln2", "final_norm")


@dataclass(frozen=True)
class Arch:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool

    @staticmethod
    def from_config(c: dict) -> "Arch":
        return Arch(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                    heads=c["num_attention_heads"],
                    kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                    d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                    rope_theta=float(c["rope_theta"]),
                    eps=float(c["rms_norm_eps"]),
                    tied=bool(c["tie_word_embeddings"]))


def weight_shapes(a: Arch) -> dict:
    L, D, F, V = a.layers, a.d_model, a.d_ff, a.vocab
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    blocks = {"ln1": (L, D), "wq": (L, D, q), "wk": (L, D, kv),
              "wv": (L, D, kv), "wo": (L, q, D), "wg": (L, D, F),
              "wu": (L, D, F), "wd": (L, F, D), "ln2": (L, D)}
    out = {"embed": (V, D), "final_norm": (D,), "blocks": blocks}
    if not a.tied:
        out["lm_head"] = (D, V)
    return out


def init_weights(a: Arch, seed: int, shardings=None):
    """Random float32 weights from ``seed``, made on the device(s) in one
    jitted call (with ``shardings``, each chip makes only its share).
    Matrices are N(0, 1/fan_in), the embedding N(0, 1/d_model); the norm gains are N(0, 0.1^2), so the
    (1 + w) gains differ from 1 and the check sees them."""
    shapes = weight_shapes(a)
    paths, tree = jax.tree.flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.key(seed % (1 << 32))

    def make(k):
        keys = jax.random.split(k, len(paths))
        leaves = []
        for kk, (path, shp) in zip(keys, paths):
            name = path[-1].key
            if name in NORMS:
                scale = 0.1
            else:
                scale = (shp[-1] if name == "embed" else shp[-2]) ** -0.5
            leaves.append(jax.random.normal(kk, shp, jnp.float32) * scale)
        return jax.tree.unflatten(tree, leaves)

    if shardings is None:
        return jax.jit(make)(key)
    return jax.jit(make, out_shardings=shardings)(key)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (xf * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """x: [B, T, H, hd] at positions 0..T-1."""
    T, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def logits(a: Arch, w: dict, tokens, precision=HIGHEST):
    """[B, T] token ids -> [B, T, V] logits, causal over each row."""
    mm = lambda x, y: jnp.matmul(x, y, precision=precision)  # noqa: E731
    B, T = tokens.shape
    H, K, hd = a.heads, a.kv_heads, a.head_dim
    g = H // K
    x = w["embed"][tokens]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"], a.eps)
        q = _rope(mm(h, p["wq"]).reshape(B, T, H, hd), a.rope_theta)
        k = _rope(mm(h, p["wk"]).reshape(B, T, K, hd), a.rope_theta)
        v = mm(h, p["wv"]).reshape(B, T, K, hd)
        q = q.reshape(B, T, K, g, hd)
        s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=precision)
        s = s.astype(jnp.float32) * hd ** -0.5
        s = jnp.where(causal, s, -1e30)
        pr = jax.nn.softmax(s, -1).astype(v.dtype)
        o = jnp.einsum("bkgqs,bskd->bqkgd", pr, v, precision=precision)
        x = x + mm(o.reshape(B, T, H * hd), p["wo"])
        h = _rms(x, p["ln2"], a.eps)
        x = x + mm(jax.nn.silu(mm(h, p["wg"])) * mm(h, p["wu"]), p["wd"])
        return x, None

    x, _ = jax.lax.scan(layer, x, w["blocks"])
    x = _rms(x, w["final_norm"], a.eps)
    head = w["embed"].T if a.tied else w["lm_head"]
    return mm(x, head).astype(jnp.float32)


def gaps(a: Arch, w: dict, tokens, targets):
    """float32 at "highest". targets: [K, B, T] token ids (-1 = no target).
    Returns [K, B, T]: best logit minus the target's logit (0 where there
    is no target)."""
    lg = logits(a, w, tokens)
    best = lg.max(-1)
    t = jnp.maximum(targets, 0)
    picked = jnp.take_along_axis(lg[None], t[..., None], -1)[..., 0]
    return jnp.where(targets >= 0, best[None] - picked, 0.0)


def argmax_low(a: Arch, w_low: dict, tokens):
    """The control: the same pass in bfloat16 (weights cast once by the
    caller), default matmul precision. Returns the [B, T] argmax."""
    return jnp.argmax(logits(a, w_low, tokens, precision=None), -1)


def to_bf16(w: dict):
    return jax.tree.map(lambda x: x.astype(jnp.bfloat16), w)


def to_int8(w: dict):
    """Weight-only int8: every matrix rounded to 255 levels per output
    channel (per row of the embedding), symmetric, then held in bfloat16;
    the norm gains stay bfloat16."""
    def q(x, axis):
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
        s = jnp.where(s > 0, s, 1.0)
        return (jnp.clip(jnp.round(x / s), -127, 127) * s).astype(
            jnp.bfloat16)

    out = to_bf16(w)
    out["embed"] = q(w["embed"], -1)
    if "lm_head" in w:
        out["lm_head"] = q(w["lm_head"], -2)
    out["blocks"] = dict(out["blocks"])
    for k, x in w["blocks"].items():
        if k not in NORMS:
            out["blocks"][k] = q(x, -2)
    return out


# the controls: the reference put in the program's place at a lower
# precision than the configuration states. The configuration states float32
# with matmuls at one bfloat16 pass ("matmul_precision": "default"), so the
# step below it is int8; bf16 (below float32 storage) is read beside it.
CONTROLS = {"bf16": to_bf16, "int8": to_int8}
