"""The flop and byte functions (a kernel's in harness/flops.py, GPT-2's own
in models/gpt2.py) against hand-worked values, and the peaks table."""

import pytest

from harness import flops, loader, peaks

gpt2 = loader.load_model("gpt2")
MEDIUM = dict(n_embd=1024, n_layer=24, n_inner=4096, vocab_size=50257,
              n_positions=1024)
LARGE = dict(n_embd=1280, n_layer=36, n_inner=5120, vocab_size=50257,
             n_positions=1024)


@pytest.mark.parametrize("sizes,matmul,total", [
    # per layer 12 d^2: 12 * 1024^2 * 24 = 301,989,888; head 50257 * 1024
    (MEDIUM, 301_989_888 + 51_463_168, 354_823_168),
    # 12 * 1280^2 * 36 = 707,788,800; head 50257 * 1280 = 64,328,960
    (LARGE, 707_788_800 + 64_328_960, 774_030_080),
])
def test_parameter_counts(sizes, matmul, total):
    assert gpt2.matmul_params(sizes) == matmul
    assert gpt2.param_count(sizes) == total


def test_train_flops_per_token_medium():
    # 6 * 353,453,056 = 2,120,718,336; attention 3 * (4 * 1024 * 1024 * 24
    # / 2) = 150,994,944
    got = gpt2.train_flops_per_token(MEDIUM, seq_len=1024)
    assert got == 2_120_718_336 + 150_994_944
    assert round(got / 1e9, 2) == 2.27


@pytest.mark.parametrize("kind,mults,tensors,stats", [
    ("fwd", 2, 4, 1), ("dq", 3, 5, 2), ("dkv", 4, 6, 2)])
def test_flash_cost_medium_shard(kind, mults, tensors, stats):
    # 8 rows x 16 heads x 1024 x 1024 x 64: one causal matmul is
    # 2 * 8 * 16 * 1024^2 * 64 / 2 = 8,589,934,592 operations; one bf16
    # tensor is 8 * 16 * 1024 * 64 * 2 = 16,777,216 bytes; one f32 row
    # statistic 8 * 16 * 1024 * 4 = 524,288 bytes.
    ops, byts = flops.flash_attention_cost(8, 16, 1024, 64, kind)
    assert ops == mults * 8_589_934_592
    assert byts == tensors * 16_777_216 + stats * 524_288


def test_flash_cost_rejects_unknown_kind():
    with pytest.raises(ValueError):
        flops.flash_attention_cost(1, 1, 128, 64, "bwd")


def test_decode_step_bytes_large_32_slots():
    # parameters as stored: 774,030,080 * 4 = 3,096,120,320 bytes; cache
    # 2 * 36 * 1280 * 2 bytes = 184,320 a token, x 32 x 1024
    param_bytes = 774_030_080 * 4
    got = gpt2.decode_step_bytes(param_bytes, LARGE, slots=32)
    assert got == 3_096_120_320 + 184_320 * 32 * 1024


def test_peaks_v5e_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s, p.hbm_bytes,
            p.ici_bits_per_s) == (197e12, 819e9, 16e9, 1600e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="not in the benchmark's peaks"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
