"""Pallas kernel validation (interpret=True on CPU): shape/dtype sweeps,
assert_allclose against the pure-jnp oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _mk_decode(B, Hq, Hkv, S, hd, dtype, filled=None, ring=False):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, Hq, hd), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, hd), dtype)
    filled = S if filled is None else filled
    if ring:
        # ring buffer: slot s holds absolute position q_pos - ((q_pos - s) % S)
        q_pos = jnp.full((B,), filled, jnp.int32)
        kv_pos = (jnp.arange(S)[None, :]
                  + (filled - S) // S * S).astype(jnp.int32)
        kv_pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S)).astype(jnp.int32)
    else:
        kv_pos = jnp.where(jnp.arange(S) < filled, jnp.arange(S), -1)
        kv_pos = jnp.broadcast_to(kv_pos, (B, S)).astype(jnp.int32)
        q_pos = jnp.full((B,), filled, jnp.int32)
    return q, k, v, kv_pos, q_pos


@pytest.mark.parametrize("B,Hq,Hkv,S,hd", [
    (2, 4, 2, 128, 32),
    (1, 8, 1, 256, 64),
    (3, 6, 6, 64, 16),     # MHA
    (2, 5, 1, 96, 32),     # odd group, S not multiple of blk -> pad path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(B, Hq, Hkv, S, hd, dtype):
    q, k, v, kv_pos, q_pos = _mk_decode(B, Hq, Hkv, S, hd, dtype, filled=S - 7)
    out = ops.decode_attention(q, k, v, kv_pos, q_pos, blk=64, interpret=True)
    want = ref.decode_attention_ref(q, k, v, kv_pos, q_pos)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_window():
    q, k, v, kv_pos, q_pos = _mk_decode(2, 4, 2, 128, 32, jnp.float32)
    out = ops.decode_attention(q, k, v, kv_pos, q_pos, window=40, blk=32,
                               interpret=True)
    want = ref.decode_attention_ref(q, k, v, kv_pos, q_pos, window=40)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_decode_attention_empty_slots():
    """Ring cache with invalid (-1) slots — fully masked blocks must not
    contribute (the exp(-inf - -inf) guard)."""
    q, k, v, kv_pos, q_pos = _mk_decode(2, 4, 2, 128, 32, jnp.float32,
                                        filled=16)
    out = ops.decode_attention(q, k, v, kv_pos, q_pos, blk=32, interpret=True)
    want = ref.decode_attention_ref(q, k, v, kv_pos, q_pos)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert not np.isnan(np.asarray(out)).any()


@pytest.mark.parametrize("B,S,Hq,Hkv,hd", [
    (2, 128, 4, 2, 32),
    (1, 256, 2, 1, 64),
    (2, 64, 3, 3, 16),
])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 48),
                                           (False, None)])
def test_flash_prefill_matches_ref(B, S, Hq, Hkv, hd, causal, window):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, hd))
    k = jax.random.normal(ks[1], (B, S, Hkv, hd))
    v = jax.random.normal(ks[2], (B, S, Hkv, hd))
    out = ops.flash_prefill(q, k, v, causal=causal, window=window,
                            qblk=32, kblk=32, interpret=True)
    want = ref.flash_prefill_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, want, rtol=3e-5, atol=3e-5)


def test_flash_prefill_bf16():
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.bfloat16)
    out = ops.flash_prefill(q, k, v, qblk=64, kblk=64, interpret=True)
    want = ref.flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (2, 64, 3, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 48, 1, 8, 4, 16),
    (1, 40, 2, 16, 8, 16),   # T not a chunk multiple -> pad path
])
def test_ssd_scan_matches_sequential(B, T, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.random.normal(ks[1], (B, T, H)) * 0.5
    b = jax.random.normal(ks[2], (B, T, N))
    c = jax.random.normal(ks[3], (B, T, N))
    a_log = jnp.log(jnp.linspace(1.0, 4.0, H))
    d_skip = jnp.ones((H,))
    dt_bias = jnp.zeros((H,))
    y, h = ops.ssd_scan(x, dt, a_log, b, c, d_skip, dt_bias, chunk=chunk,
                        interpret=True)
    y_ref, h_ref = ref.ssd_scan_sequential_ref(x, dt, a_log, b, c, d_skip,
                                               dt_bias)
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h, h_ref, rtol=2e-4, atol=2e-4)


def test_ssd_scan_matches_chunked_jnp():
    B, T, H, P, N = 2, 96, 2, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.random.normal(ks[1], (B, T, H)) * 0.5
    b = jax.random.normal(ks[2], (B, T, N))
    c = jax.random.normal(ks[3], (B, T, N))
    a_log = jnp.zeros((H,))
    d_skip = jnp.zeros((H,))
    dt_bias = jnp.zeros((H,))
    y1, h1 = ops.ssd_scan(x, dt, a_log, b, c, d_skip, dt_bias, chunk=32,
                          interpret=True)
    y2, h2 = ref.ssd_scan_ref(x, dt, a_log, b, c, d_skip, dt_bias, chunk=32)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)


def test_model_with_ssd_kernel_matches_jnp_path():
    """ModelOptions(use_ssd_kernel=True) must reproduce the jnp forward."""
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config("mamba2-780m").reduced()
    p = M.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 24), 0, cfg.vocab_size)
    ref_logits, _ = M.forward(cfg, p, toks)
    k_logits, _ = M.forward(cfg, p, toks, M.ModelOptions(use_ssd_kernel=True))
    np.testing.assert_allclose(k_logits, ref_logits, rtol=2e-3, atol=2e-3)


# ------------------------------------------------------------- SSD decode

def _mk_ssd_step(B, H, P, N):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, H, P))
    dt = jax.random.normal(ks[1], (B, H)) * 0.5
    b = jax.random.normal(ks[2], (B, N))
    c = jax.random.normal(ks[3], (B, N))
    h = jax.random.normal(ks[4], (B, H, P, N)).astype(jnp.float32)
    a_log = jnp.log(jnp.linspace(1.0, 4.0, H))
    d_skip = jnp.ones((H,))
    dt_bias = jnp.zeros((H,))
    return x, dt, a_log, b, c, d_skip, dt_bias, h


@pytest.mark.parametrize("B,H,P,N", [(1, 2, 16, 8), (3, 4, 8, 16)])
def test_ssd_decode_step_kernel_matches_ref(B, H, P, N):
    args = _mk_ssd_step(B, H, P, N)
    y, h1 = ops.ssd_decode_step(*args, interpret=True)
    y_ref, h_ref = ref.ssd_decode_step_ref(*args)
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h1, h_ref, rtol=2e-5, atol=2e-5)


def test_ssd_decode_recurrence_matches_chunked_c1():
    """The single-token decode recurrence and the chunked (dual-form)
    prefill are the SAME operator: chunk=1 prefill == repeated ssd_step,
    token for token and final state for final state."""
    from repro.models.ssm import ssd_chunked, ssd_step
    B, T, H, P, N = 2, 12, 2, 8, 4
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.random.normal(ks[1], (B, T, H)) * 0.5
    b = jax.random.normal(ks[2], (B, T, N))
    c = jax.random.normal(ks[3], (B, T, N))
    a_log = jnp.log(jnp.linspace(1.0, 3.0, H))
    d_skip = jnp.ones((H,))
    dt_bias = jnp.zeros((H,))
    y_chunk, h_chunk = ssd_chunked(x, dt, a_log, b, c, d_skip, dt_bias,
                                   chunk=1)
    h = jnp.zeros((B, H, P, N), jnp.float32)
    ys = []
    for t in range(T):
        y_t, h = ssd_step(x[:, t], dt[:, t], a_log, b[:, t], c[:, t],
                          d_skip, dt_bias, h)
        ys.append(y_t)
    np.testing.assert_allclose(y_chunk, jnp.stack(ys, 1),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(h_chunk, h, rtol=2e-5, atol=2e-5)


def test_ssd_chunked_state_carry_matches_full():
    """Split prefill with h0 carry == one-shot prefill (the chunked ==
    recurrent equivalence the paged engine's chunk path relies on)."""
    from repro.models.ssm import ssd_chunked
    B, T, H, P, N = 1, 32, 2, 8, 4
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.random.normal(ks[1], (B, T, H)) * 0.5
    b = jax.random.normal(ks[2], (B, T, N))
    c = jax.random.normal(ks[3], (B, T, N))
    a_log = jnp.log(jnp.linspace(1.0, 3.0, H))
    d_skip = jnp.ones((H,))
    dt_bias = jnp.zeros((H,))
    y_full, h_full = ssd_chunked(x, dt, a_log, b, c, d_skip, dt_bias,
                                 chunk=8)
    cut = 12                             # deliberately not a chunk multiple
    y1, h1 = ssd_chunked(x[:, :cut], dt[:, :cut], a_log, b[:, :cut],
                         c[:, :cut], d_skip, dt_bias, chunk=8)
    y2, h2 = ssd_chunked(x[:, cut:], dt[:, cut:], a_log, b[:, cut:],
                         c[:, cut:], d_skip, dt_bias, chunk=8, h0=h1)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full,
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h2, h_full, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ MoE grouped

def test_moe_grouped_ffn_kernel_matches_ref():
    E, C, D, F = 4, 6, 16, 32
    ks = jax.random.split(KEY, 4)
    buf = jax.random.normal(ks[0], (E, C, D))
    wg = jax.random.normal(ks[1], (E, D, F)) * D ** -0.5
    wu = jax.random.normal(ks[2], (E, D, F)) * D ** -0.5
    wd = jax.random.normal(ks[3], (E, F, D)) * F ** -0.5
    out = ops.moe_grouped_ffn(buf, wg, wu, wd, interpret=True)
    np.testing.assert_allclose(out, ref.moe_grouped_ffn_ref(buf, wg, wu, wd),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("skew", [0.0, 4.0])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_moe_grouped_decode_matches_dense(skew, use_kernel):
    """Grouped decode dispatch == dense all-experts oracle, including when
    routing is heavily skewed (uneven expert loads: with skew=4 nearly
    every token lands on expert 0, leaving other groups near-empty)."""
    from repro.models.moe import (init_moe_params, moe_ffn_dense,
                                  moe_ffn_grouped_decode, route)
    B, D, F, E, K = 7, 16, 32, 5, 2
    p = init_moe_params(KEY, D, F, E)
    p = p._replace(router=p.router.at[:, 0].add(skew * D ** -0.5))
    # tokens share a positive mean, so the column-0 shift above raises
    # expert 0's logit for every token (sum(x) > 0) instead of for an
    # rng-dependent subset
    x = jax.random.normal(jax.random.PRNGKey(3), (B, D)) + 1.0
    if skew:
        _, ids, _ = route(p.router, x, K)
        loads = np.bincount(np.asarray(ids).ravel(), minlength=E)
        assert loads.max() >= 2 * loads.min() + 1, loads  # genuinely uneven
    y_g, _ = moe_ffn_grouped_decode(p, x, K, use_kernel=use_kernel)
    y_d, _ = moe_ffn_dense(p, x, K)
    np.testing.assert_allclose(y_g, y_d, rtol=2e-5, atol=2e-5)


def test_auto_interpret_refuses_other_backends(monkeypatch):
    """interpret=None interprets on CPU, compiles on TPU and raises
    elsewhere instead of quietly running the interpreter."""
    for backend, want in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert ops._auto_interpret(None) is want
        assert ops._auto_interpret(not want) is (not want)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._auto_interpret(None)
    assert ops._auto_interpret(True) is True
