
import pytest

import run
from work import decode, fabric

MIX = run.load_json(run.BENCH / "configs" / "tiara-paper-mix.json")
LM = run.load_json(run.BENCH / "configs" / "internlm2-1.8b-tiara.json")


@pytest.mark.parametrize("tenant,params,want", [
    ("graph", (0, 10, 0), 10 * 8 + 8 + 2 * 64),
    ("graph", (8, 0, 8), 8 + 2 * 64),
    ("ptw", (1 << 21, 512), 3 * 8 + 2 * 4096),
    ("lock", (0, 1, 7, 0, 1, 0, 1), 9 * 8),
    ("kv", (3, 0), 3 * (16 + 2 * 8192)),
    ("moe", (4, 0), 4 * (16 + 2 * 8192)),
])
def test_post_bytes_from_parameters_alone(tenant, params, want):
    assert fabric.post_bytes(MIX, tenant, params) == want


def test_decode_weight_bytes_are_the_published_weights():
    layer = (2048 * 2048 + 2 * 2048 * 1024 + 2048 * 2048
             + 3 * 2048 * 8192)
    elems = 24 * (layer + 2 * 2048) + 2048 + 2048 * 92544
    assert decode.weight_bytes(LM, 0) == 2 * elems
    # 1.89e9 parameters with the embedding, about 3.4 GB read a step
    assert 3.3e9 < decode.weight_bytes(LM, 32) < 3.5e9


def test_kv_bytes_count_each_sequence_up_to_its_length():
    per_token = 2 * 8 * 128 * 2 * 24
    assert decode.kv_bytes(LM, [1, 100]) == 101 * per_token
    assert decode.decode_bytes(LM, [5]) == \
        decode.weight_bytes(LM, 1) + decode.kv_bytes(LM, [5])


def test_step_flops_count_decodes_and_prefills():
    lm = 24 * decode.layer_matmul_params(LM)
    head = 2048 * 92544
    att = 4 * 24 * 16 * 128
    assert decode.step_flops(LM, [], [10]) == 2 * (lm + head) + att * 10
    assert decode.step_flops(LM, [4], []) == \
        2 * lm * 4 + 2 * head + att * 10
