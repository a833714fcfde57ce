"""Compile the served path for a described TPU v5e chip, with no chip.

The TPU compiler refuses what interpret mode accepts: blocks not aligned
to the tiling, kernels over their VMEM budget, programs over HBM. These
tests compile the three Pallas kernels the paged engine routes through
(``--paged-kernel``, ``--prefill-chunk``, spec-decode verify) and the
full-width decode step at smollm-360m's published widths, for one chip of
a described ``v5e:2x2`` topology. Nothing runs, so they say nothing about
results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
pytest worker imports every test file. Keep these tests in this one file.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.models import model as M

CFG = get_config("smollm-360m")
N_PAGES, PAGE, MAX_SEQ = 1024, 16, 256      # chip_smoke.py's served engine
MAXP = MAX_SEQ // PAGE
HBM_BYTES = 16e9                            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    return compiled.as_text()


def _kv_pages(sh):
    shape = (N_PAGES, CFG.n_kv_heads, PAGE, CFG.head_dim)
    return _spec(sh, shape), _spec(sh, shape)


@pytest.mark.parametrize("B", [1, 16])
def test_paged_decode_attention_compiles(one_chip, B):
    k, v = _kv_pages(one_chip)
    hlo = _compile(
        lambda q, k, v, pt, qp: ops.paged_decode_attention(
            q, k, v, pt, qp, interpret=False),
        _spec(one_chip, (B, CFG.n_heads, CFG.head_dim)), k, v,
        _spec(one_chip, (B, MAXP), jnp.int32),
        _spec(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("B,C", [(1, 2), (16, 5)])
def test_paged_verify_attention_compiles(one_chip, B, C):
    k, v = _kv_pages(one_chip)
    hlo = _compile(
        lambda q, k, v, pt, qs: ops.paged_verify_attention(
            q, k, v, pt, qs, interpret=False),
        _spec(one_chip, (B, C, CFG.n_heads, CFG.head_dim)), k, v,
        _spec(one_chip, (B, MAXP), jnp.int32),
        _spec(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("C", [1, 32])
def test_flash_prefill_chunk_compiles(one_chip, C):
    kv = (1, MAX_SEQ, CFG.n_kv_heads, CFG.head_dim)
    hlo = _compile(
        lambda q, k, v, qs: ops.flash_prefill_chunk(q, k, v, qs,
                                                    interpret=False),
        _spec(one_chip, (1, C, CFG.n_heads, CFG.head_dim)),
        _spec(one_chip, kv), _spec(one_chip, kv),
        _spec(one_chip, (1,), jnp.int32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("use_kernel", [False, True])
def test_full_width_decode_step_compiles(one_chip, use_kernel, monkeypatch):
    """The paged engine's decode step at 16 rows over a 1024-page arena.
    This process sees the CPU backend, where the kernel wrappers pick
    interpret mode; the kernel variant is steered to the chip's Mosaic
    lowering here."""
    if use_kernel:
        monkeypatch.setattr(ops, "_auto_interpret",
                            lambda interpret: bool(interpret))

    def shapes(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)

    params = shapes(jax.eval_shape(
        lambda: M.init_params(CFG, jax.random.PRNGKey(0))))
    pages = shapes(jax.eval_shape(
        lambda: M.init_paged_cache(CFG, N_PAGES, PAGE)))
    B = 16
    hlo = _compile(
        lambda p, g, pt, ln, tk, av: M.decode_step_paged(
            CFG, p, g, pt, ln, tk, av, use_kernel=use_kernel),
        params, pages, _spec(one_chip, (B, MAXP), jnp.int32),
        _spec(one_chip, (B,), jnp.int32), _spec(one_chip, (B,), jnp.int32),
        _spec(one_chip, (B,), jnp.bool_))
    assert ("tpu_custom_call" in hlo) == use_kernel


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_served_steps_update_the_arena_in_place(one_chip, kind,
                                                       monkeypatch):
    """The benchmark cell's engine (32 rows, 2048 pages, max_seq 1024,
    128-token chunks, both kernels) as the executor builds it: the arena
    donated, at the TPU's default layout for its lane-padded shape. That
    layout is row-major, the one the paged kernel reads (at head_dim 64
    unpadded it would put the pages dimension minor); both arenas alias
    outputs, no arena-shaped copy is made and no temp buffer is as large
    as one arena — no step relayouts or rebuilds the arena."""
    import re

    monkeypatch.setattr(ops, "_auto_interpret",
                        lambda interpret: bool(interpret))
    n_pages, maxp, B, C = 2048, 1024 // PAGE, 32, 128

    def shapes(tree):
        return jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                            tree)

    params = shapes(jax.eval_shape(
        lambda: M.init_params(CFG, jax.random.PRNGKey(0))))
    pages = shapes(jax.eval_shape(
        lambda: M.init_paged_cache(CFG, n_pages, PAGE)))
    if kind == "decode":
        fn = lambda p, g, pt, ln, tk: M.decode_step_paged(  # noqa: E731
            CFG, p, g, pt, ln, tk, use_kernel=True)
        rows, toks = B, (B,)
    else:
        fn = lambda p, g, pt, ln, tk: M.prefill_chunk_paged(  # noqa: E731
            CFG, p, g, pt, ln, tk, use_kernel=True)
        rows, toks = 1, (1, C)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, pages, _spec(one_chip, (rows, maxp), jnp.int32),
        _spec(one_chip, (rows,), jnp.int32),
        _spec(one_chip, toks, jnp.int32)).compile()
    arena = pages["k_pages"]
    assert arena.shape[-1] == 128            # head_dim 64, lane-padded
    for name in ("k_pages", "v_pages"):
        fmt = compiled.input_formats[0][1][name]
        assert fmt.layout.major_to_minor == tuple(range(5)), name
    arena_bytes = arena.size * 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * arena_bytes
    assert mem.temp_size_in_bytes < arena_bytes
    hlo = compiled.as_text()
    shape = "f32[%s]" % ",".join(map(str, arena.shape))
    assert not re.search(re.escape(shape) + r"\{[^}]*\} copy\(", hlo)
    assert "tpu_custom_call" in hlo
