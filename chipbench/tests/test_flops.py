import pytest

import flops
from references.llama_dense import Arch

A = Arch(layers=2, d_model=8, heads=4, kv_heads=2, head_dim=2, d_ff=16,
         vocab=32, rope_theta=1e4, eps=1e-5, tied=False)


def test_matmul_flops_by_hand():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8, MLP 3 x 8x16; head 8x32
    per_layer = 64 + 32 + 32 + 64 + 3 * 128
    assert flops.matmul_flops_per_token(A) == 2 * (2 * per_layer + 256)


def test_attention_and_steps_by_hand():
    # one query over 10 keys: QK and PV, 2*4 heads*2 dims each, 2 layers
    assert flops.attn_flops(A, 10) == 2 * 2 * (2 * 4 * 2 * 10)
    per_tok = flops.matmul_flops_per_token(A)
    assert flops.decode_step_flops(A, [5, 7]) == \
        2 * per_tok + flops.attn_flops(A, 12)
    # queries at positions 3,4,5 after 3 cached tokens see 4,5,6 keys
    assert flops.causal_keys(3, 3) == 15
    assert flops.chunk_step_flops(A, 3, 3) == \
        3 * per_tok + flops.attn_flops(A, 15)


def test_kernel_costs_by_hand():
    f, b = flops.paged_decode_kernel_cost(A, [5, 7])
    assert f == 4 * 4 * 2 * 12
    assert b == 4 * 2 * (2 * 2 * 12 + 2 * 4 * 2)
    f, b = flops.flash_chunk_kernel_cost(A, 3, 3)
    assert f == 4 * 4 * 2 * 15
    assert b == 4 * 2 * (2 * 2 * 6 + 2 * 4 * 3)
    f, b = flops.paged_decode_kernel_cost(A, [5, 7])
    f2, b2 = flops.paged_decode_kernel_cost(A, [5, 7], shards=2)
    assert (f2, b2) == (f / 2, b / 2)


@pytest.mark.parametrize("fl,by,which", [(197e12, 1.0, "compute"),
                                         (1.0, 819e9, "memory")])
def test_roofline_picks_the_binding_bound(fl, by, which):
    t, bound = flops.roofline_s(fl, by, 197e12, 819e9)
    assert bound == which and t == pytest.approx(1.0)
