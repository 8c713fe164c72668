"""The matmul-only operation count against numbers worked by hand."""

import pytest

from benchmark.spec import Benchmark

BENCH = Benchmark()
FLOPS = BENCH.flops("dense_lm")


def test_internlm2_cut_to_four_layers():
    c = BENCH.config("internlm2-1.8b")
    # a layer: wq 2048*2048 + wk, wv 2 * 2048*1024 + wo 2048*2048 = 12,582,912
    #          gate, up, down 3 * 2048*8192              = 50,331,648   -> 62,914,560
    # head: 2048 * 92,544 = 189,530,112
    assert FLOPS.matmul_params(c) == 4 * 62_914_560 + 189_530_112 == 441_188_352
    # with the embedding (189,530,112) and 9 norm vectors of 2048: the parameter count
    assert FLOPS.total_params(c) == 441_188_352 + 189_530_112 + 9 * 2048 == 630_736_896
    # attention: per layer 3 * 2 * (2 * 16 * 128 * 2048.5) = 50,343,936 a token
    assert FLOPS.attention_flops_per_token(c, 4096) == pytest.approx(4 * 50_343_936)
    assert FLOPS.train_flops_per_token(c, 4096) == pytest.approx(6 * 441_188_352 + 201_375_744)
    # the count the repo's own benches use over-counts by the embedding's share
    assert 6 * FLOPS.total_params(c) / (6 * FLOPS.matmul_params(c)) == pytest.approx(1.4296, abs=1e-3)


def test_mistral_cut_to_two_layers():
    c = BENCH.config("mistral-7b")
    # a layer: 2 * 4096*4096 + 2 * 4096*1024 = 41,943,040; 3 * 4096*14,336 = 176,160,768 -> 218,103,808
    assert FLOPS.matmul_params(c) == 2 * 218_103_808 + 4096 * 32_000 == 567_279_616
    assert FLOPS.total_params(c) == 698_372_096
    assert FLOPS.train_flops_per_token(c, 4096) == pytest.approx(6 * 567_279_616 + 2 * 3 * 2 * 2 * 32 * 128 * 2048.5)
    assert FLOPS.train_flops_per_token(c, 4096) / 1e9 == pytest.approx(3.605, abs=1e-3)


def test_kernel_counts_from_shapes():
    c, t = BENCH.config("internlm2-1.8b"), BENCH.traffic("steady-1g")
    fa = BENCH.flops("tpuft_fa").per_step(c, t)
    # 4 layers * (2 * 16) heads * 6 matmuls * 2 * 4096 * 4097 / 2 * 128
    assert fa["flops"] == pytest.approx(4 * 32 * 6 * 2 * 4096 * 4097 / 2 * 128)
    assert fa["bytes"] == 4 * 32 * (12 * 4096 * 128 * 2 + 3 * 4096 * 4)
    ce = BENCH.flops("tpuft_ce").per_step(c, t)
    assert ce["flops"] == 2 * 2 * 8192 * 2048 * 92_544
    assert ce["bytes"] == 2 * (8192 * 2048 * 2 + 2048 * 92_544 * 2) + 3 * 8192 * 4 + 8192 * 92_544 * 2
    # both are compute-bound on a v5e by these counts
    peaks = BENCH.peaks("TPU v5 lite")
    for need in (fa, ce):
        assert need["flops"] / peaks["bf16_flops_per_s"] > need["bytes"] / peaks["hbm_bytes_per_s"]


def test_an_unknown_device_has_no_peak():
    with pytest.raises(RuntimeError):
        BENCH.peaks("TPU v9")
    with pytest.raises(RuntimeError):
        BENCH.peaks("_source")
