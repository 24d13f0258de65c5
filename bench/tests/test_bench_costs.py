"""Work counts against hand-worked numbers at the published widths."""
import json

import pytest

from bench import costs, harness, model
from bench.families import dense

STABLELM = model.from_config(json.loads(
    (harness.BENCH / "configs" / "stablelm-1.6b-zoo4.json").read_text()))


def test_stablelm_widths():
    m = STABLELM
    assert (m.n_layers, m.d_model, m.n_heads, m.head_dim, m.d_ff, m.vocab) == (
        24, 2048, 32, 64, 5632, 100352)


@pytest.mark.parametrize("fn, args, want", [
    # 4 rows x 128 tokens x 32 heads of 64: 128*129/2 = 8256 causal pairs;
    # 4*64*4*32*8256 FLOPs; q, k, v, o of 4*128*2048 bf16 each
    (costs.flash_attention, (4, 128, 32, 32, 64), (270_532_608, 8_388_608)),
    # the four StableLM heads over a 4 x 128 micro-batch: 0.84 TFLOP;
    # x 4*512*2048 bf16 + w 4*2048*100352 bf16 + out 4*512*100352 f32
    (costs.bank_matmul, (4, 512, 2048, 100352),
     (841_813_590_016, 8_388_608 + 1_644_167_168 + 822_083_584)),
    # one head over a single 128-token request: 2*128*2048*100352 FLOPs
    (costs.bank_matmul, (1, 128, 2048, 100352),
     (52_613_349_376, 524_288 + 411_041_792 + 51_380_224)),
    # two rows attending 100 and 28 positions, 32 heads of 64: 4*64*32*128
    # FLOPs; K and V of 128 positions (2*32*128*64 bf16) plus each row's
    # query and output (2*2*32*64 bf16)
    (costs.decode_attention, ([100, 28], 32, 32, 64),
     (1_048_576, 1_048_576 + 16_384)),
    # nine pages of 16 positions x 32 heads x 64 bf16 (64 KiB), read and
    # written once
    (costs.page_gather, (9, 16 * 32 * 64 * 2), (0, 1_179_648)),
])
def test_kernel_counts(fn, args, want):
    assert fn(*args) == want


def test_dense_model_step():
    m = STABLELM
    # per layer: q, k, v, o 4 x 2*2048*2048; gated FFN 3 x 2*2048*5632
    assert dense.token_flops(m, 0) == 24 * 102_760_448
    assert dense.token_flops(m, 100) - dense.token_flops(m, 99) == 24 * 4 * 2048
    # a 128-token document: 128 tokens of matmuls plus 8256 attended pairs
    assert sum(dense.token_flops(m, n) for n in range(1, 129)) == 317_303_291_904
    assert costs.head_token_flops(m) == 411_041_792


def test_dense_decode_step_work():
    m = STABLELM
    # rows at 100 and 28 positions hold 7 and 2 pages of 16: each layer
    # gathers them for K and for V, and attends once
    work = dense.decode_work(m, [100, 28], 16)
    assert work == {"decode_attention": (24 * 1_048_576, 24 * 1_064_960),
                    "page_gather": (0, 48 * 1_179_648)}
