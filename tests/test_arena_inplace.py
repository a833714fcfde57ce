"""The paged KV arena is owned by the compiled step (DESIGN.md §3): every
step that returns pages is built with the arena donated, carries the whole
[L,P,Hkv,psz,W] arena through its layer loop and writes each layer's new
K/V into it in place. These tests hold the compiled programs to that —
the arena operands alias outputs, no arena-sized buffer is made besides
them, the arrays passed in are deleted by the call — and hold the new
dataflow to the per-layer one it replaced: the same logits and the same
arena, bit for bit."""
import re

import numpy as np
import pytest

from repro.core.task import qa_task

from helpers import make_paged_engine, reduced_cfg, sharded_test_cfg

# arena-shaped instructions that make no buffer of their own: the entry
# parameter and the loop plumbing around it, and the in-place updates of
# the carried arena (their output is the operand's buffer — which the
# memory analysis below confirms: no temp buffer as large as one arena)
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "bitcast"}
_IN_PLACE = {"scatter", "dynamic-update-slice"}


def _computations(hlo: str):
    """{computation name: (root opcode, [(opcode, shape, calls)])}."""
    comps, name, ops, root = {}, None, [], None
    head = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
    inst = re.compile(r"^\s+(ROOT )?%?[\w.\-]+ = (\S+?)(?:\{[^}]*\})? "
                      r"([\w\-]+)\(")
    for line in hlo.splitlines():
        m = head.match(line)
        if m:
            name, ops, root = m.group(1), [], None
            continue
        if line.startswith("}") and name is not None:
            comps[name] = (root, ops)
            name = None
            continue
        m = inst.match(line)
        if m and name is not None:
            calls = re.search(r"calls=%?([\w.\-]+)", line)
            ops.append((m.group(3), m.group(2), calls and calls.group(1)))
            if m.group(1):
                root = m.group(3)
    return comps


def _arena_buffers(hlo: str, shape) -> list:
    """Arena-shaped instructions that would make a buffer of their own."""
    want = "f32[%s]" % ",".join(map(str, shape))
    comps = _computations(hlo)
    bad = []
    for cname, (_, ops) in comps.items():
        for op, shp, calls in ops:
            if shp != want or op in _NO_BUFFER | _IN_PLACE:
                continue
            if op == "fusion" and comps.get(calls, (None,))[0] in _IN_PLACE:
                continue
            bad.append(f"{cname}: {op}")
    return bad


def _aliased_arena_params(hlo: str, shape) -> tuple:
    """(entry parameter numbers of arena shape, aliased parameter numbers)."""
    want = "f32[%s]" % ",".join(map(str, shape))
    entry = hlo[hlo.index("\nENTRY"):]
    params = {int(n) for shp, n in re.findall(
        r"= (f32\[[\d,]+\])\{[^}]*\} parameter\((\d+)\)", entry)
        if shp == want}
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo)
    aliased = {int(n) for n in re.findall(r"\((\d+), \{\}",
                                          alias.group(1))} if alias else set()
    return params, aliased


def _steps(ex):
    for b, c in ex._step_jit.items():
        yield f"decode b={b}", c
    for n, c in ex._chunk_jit.items():
        yield f"chunk c={n}", c
    for (b, k), c in ex._verify_jit.items():
        yield f"verify b={b} k={k}", c


@pytest.mark.parametrize("on_mesh", [False, True], ids=["one_device",
                                                        "mesh4"])
def test_every_pages_step_updates_the_arena_in_place(request, on_mesh):
    mesh = request.getfixturevalue("mesh4") if on_mesh else None
    cfg = sharded_test_cfg(ways=4) if on_mesh else reduced_cfg()
    # 1024 pages: one arena outweighs every other buffer of these steps,
    # so "no temp buffer as large as an arena" is a sharp test
    ex = make_paged_engine(cfg, n_pages=1024, page_size=16, max_seq=64,
                           max_batch=4, prefill_chunk_size=8,
                           spec_decode=True, max_spec_depth=2, mesh=mesh)
    arena = ex.pages["k_pages"]
    shard = arena.sharding.shard_shape(arena.shape)   # per device
    shard_bytes = int(np.prod(shard)) * arena.dtype.itemsize
    names = []
    for name, compiled in _steps(ex):
        names.append(name)
        hlo = compiled.as_text()
        params, aliased = _aliased_arena_params(hlo, shard)
        assert len(params) == 2 and params <= aliased, (name, params,
                                                        aliased)
        assert _arena_buffers(hlo, shard) == [], name
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= 2 * shard_bytes, name
        assert mem.temp_size_in_bytes < shard_bytes, (name,
                                                      mem.temp_size_in_bytes)
    # one decode bucket per power of two, one chunk bucket per piece size,
    # one verify bucket per (batch, depth) pair
    assert len(names) == 3 + 4 + 3 * 2

    # each kind of call consumes the arena it is given
    t = qa_task(prompt_len=12, output_len=8)
    calls = [lambda: ex.prefill_chunk(t, 8),
             lambda: ex.prefill_chunk(t, 4),
             lambda: ex.decode([t]),
             lambda: ex.decode([t], depths=[2])]
    for call in calls:
        before = list(ex.pages.values())
        call()
        ex.drain()
        assert all(a.is_deleted() for a in before)
        assert not any(a.is_deleted() for a in ex.pages.values())
    ex.release(t)
    ex.pool.check()


# ------------------------------------------------------------ equivalence

def _slab_scan(body, x, params, pages):
    """Run ``body`` over the layers with the arena as the scan's xs: each
    layer's slab is sliced out and the slabs are restacked on the way out."""
    import jax

    x, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], pages["k_pages"], pages["v_pages"]))
    return x, {"k_pages": k_new, "v_pages": v_new}


def _reference_chunk(cfg, params, pages, page_table, lengths, tokens):
    """prefill_chunk_paged in the dataflow it replaced: per layer, slice
    the slab, scatter the chunk's K/V (zero-padded to the arena's lane
    width) into it, run the jnp oracle."""
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import model as M

    B, C = tokens.shape
    n_pages, psz, W = (pages["k_pages"].shape[1], pages["k_pages"].shape[3],
                       pages["k_pages"].shape[4])
    x = params["embed"][tokens]
    q_pos = lengths[:, None] + jnp.arange(C, dtype=lengths.dtype)
    pt_row = page_table[jnp.arange(B)[:, None], q_pos // psz]
    phys = jnp.where(pt_row >= 0, pt_row, n_pages)
    off = q_pos % psz

    def body(x, xs):
        bp, kp, vp = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        q = (h @ bp["wq"]).reshape(B, C, cfg.n_heads, cfg.head_dim)
        k = (h @ bp["wk"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ bp["wv"]).reshape(B, C, cfg.n_kv_heads, cfg.head_dim)
        q = L.apply_rope(q, q_pos, cfg.rope_theta)
        k = L.apply_rope(k, q_pos, cfg.rope_theta)
        kp = kp.at[phys, :, off].set(L.lane_pad(k, W), mode="drop")
        vp = vp.at[phys, :, off].set(L.lane_pad(v, W), mode="drop")
        hd = cfg.head_dim
        a = L.chunk_decode_attention(
            q, L.gather_pages(kp, page_table)[..., :hd],
            L.gather_pages(vp, page_table)[..., :hd],
            L.paged_kv_positions(page_table, psz), q_pos)
        x = x + a.reshape(B, C, cfg.q_dim) @ bp["wo"]
        f_out, _ = M._ffn(cfg, bp, x, "grouped")
        return x + f_out, (kp, vp)

    x, new = _slab_scan(body, x, params, pages)
    return M.unembed(cfg, params, x[:, -1]), new


def _reference_decode(cfg, params, pages, page_table, lengths, tokens,
                      active):
    """decode_step_paged in the dataflow it replaced (dense attention
    archs): per layer, slice the slab, scatter the token, jnp oracle."""
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import model as M

    B = tokens.shape[0]
    n_pages, psz, W = (pages["k_pages"].shape[1], pages["k_pages"].shape[3],
                       pages["k_pages"].shape[4])
    x = params["embed"][tokens]
    q_pos = lengths
    pt_row = page_table[jnp.arange(B), q_pos // psz]
    phys = jnp.where(active & (pt_row >= 0), pt_row, n_pages)
    off = q_pos % psz

    def body(x, xs):
        bp, kp, vp = xs
        h = L.rms_norm(x, bp["ln1"], cfg.norm_eps)
        q = (h @ bp["wq"]).reshape(B, cfg.n_heads, cfg.head_dim)
        k = (h @ bp["wk"]).reshape(B, cfg.n_kv_heads, cfg.head_dim)
        v = (h @ bp["wv"]).reshape(B, cfg.n_kv_heads, cfg.head_dim)
        q = L.apply_rope(q[:, None], q_pos[:, None], cfg.rope_theta)[:, 0]
        k = L.apply_rope(k[:, None], q_pos[:, None], cfg.rope_theta)[:, 0]
        kp = kp.at[phys, :, off].set(L.lane_pad(k, W), mode="drop")
        vp = vp.at[phys, :, off].set(L.lane_pad(v, W), mode="drop")
        a = L.paged_decode_attention(q, kp, vp, page_table, q_pos)
        x = x + a.reshape(B, cfg.q_dim) @ bp["wo"]
        f_out, _ = M._ffn(cfg, bp, x, "dense")
        return x + f_out, (kp, vp)

    x, new = _slab_scan(body, x, params, pages)
    return M.unembed(cfg, params, x), new


def test_carried_arena_matches_the_per_layer_dataflow():
    """A prefill, two chunk pieces and three decode steps: the logits and
    the whole returned arena equal the per-layer reference exactly."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers as L
    from repro.models import model as M

    cfg = reduced_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    P, psz, maxp = 24, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    # the arena's own shape, head_dim padded to whole 128-lane rows
    shape = jax.eval_shape(
        lambda: M.init_paged_cache(cfg, P, psz))["k_pages"].shape
    assert shape[-1] > cfg.head_dim
    # stale contents everywhere, padding lanes too: every byte a step
    # should not touch is one the comparison would catch it touching
    pages = {"k_pages": jax.random.normal(ks[0], shape),
             "v_pages": jax.random.normal(ks[1], shape)}
    table = np.full((4, maxp), -1, np.int32)
    table[0, :4] = [5, 17, 2, 11]            # row 0: prefilled
    table[1, :4] = [9, 0, 21, 14]            # row 1: chunked
    # row 2 holds pages but sits out the decodes; row 3 is a pad row
    table[2, :2] = [3, 7]

    # prefill row 0 (13 tokens) and splice it in, as the executor does
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 13), 0,
                                cfg.vocab_size)
    _, cache = M.prefill(cfg, params, prompt, buf_len=4 * psz)
    for name, src in (("k_pages", cache["k"]), ("v_pages", cache["v"])):
        view = (src[:, 0, :, :2 * psz].reshape(cfg.n_layers, -1, 2, psz,
                                                cfg.head_dim)
                .swapaxes(1, 2))
        pages[name] = pages[name].at[:, jnp.asarray([5, 17])].set(
            L.lane_pad(view, shape[-1]))
    ref_pages = dict(pages)

    chunk = jax.jit(lambda p, g, pt, ln, tk: M.prefill_chunk_paged(
        cfg, p, g, pt, ln, tk))
    decode = jax.jit(lambda p, g, pt, ln, tk, av: M.decode_step_paged(
        cfg, p, g, pt, ln, tk, av))
    ref_chunk = jax.jit(_reference_chunk, static_argnums=0)
    ref_decode = jax.jit(_reference_decode, static_argnums=0)

    def same(got, want):
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(want[0]))
        for name in ("k_pages", "v_pages"):
            np.testing.assert_array_equal(np.asarray(got[1][name]),
                                          np.asarray(want[1][name]))
        return got[1], want[1]

    # two chunk pieces of row 1: 11 tokens from 0, then 9 from 11 (each
    # crosses a page boundary)
    pt1 = jnp.asarray(table[1:2])
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 20), 0,
                              cfg.vocab_size)
    for start, n in ((0, 11), (11, 9)):
        ln = jnp.asarray([start], jnp.int32)
        tk = toks[:, start:start + n]
        pages, ref_pages = same(chunk(params, pages, pt1, ln, tk),
                                ref_chunk(cfg, params, ref_pages, pt1, ln,
                                          tk))

    pt = jnp.asarray(table)
    lengths = np.array([13, 20, 9, 0], np.int32)
    active = jnp.asarray([True, True, False, False])
    for step in range(3):
        tk = jax.random.randint(jax.random.PRNGKey(10 + step), (4,), 0,
                                cfg.vocab_size)
        ln = jnp.asarray(lengths)
        pages, ref_pages = same(
            decode(params, pages, pt, ln, tk, active),
            ref_decode(cfg, params, ref_pages, pt, ln, tk, active))
        lengths[:2] += 1
