"""flops.py and peaks.json: the algorithm's counts against hand-computed
values for the Chameleon audio preset, and peaks only by device kind."""

import json

import pytest
from chip_tiny import ROOT

from benchmarks.chip import flops

AUDIO = json.loads((ROOT / "benchmarks/chip/configs/chameleon-tcn-audio.json")
                   .read_text())["model"]


def test_audio_sample_flops():
    # block 0: 7*1*24 + 7*24*24 + 1x1 downsample 1*24 = 4224 MACs
    # blocks 1..11: 2 * 7*24*24 = 8064 MACs each
    # heads: 24*64 embedding + 64*12 classes + 64*5 tenant ways = 2624
    macs = 4224 + 11 * 8064 + 2624
    assert macs == 95_552
    assert flops.sample_flops(AUDIO, n_ways=5) == 2 * macs


def test_block_bytes_by_hand():
    # block 11 of the audio preset: d=2048, 24 -> 24, one 160-sample chunk
    # taps touch min(7*160, 6*2048 + 160) = 1120 rows of each conv's input
    rows = 1120
    elems = rows * (24 + 24) + (7 * 24 * 24 + 24) * 2 + 2 * 160 * 24
    assert flops.block_bytes(160, 24, 24, 7, 2048) == 4 * elems == 278_208
    # block 0: d=1, 1 -> 24: 6 + 160 = 166 rows, and the downsample
    elems0 = 166 * (1 + 24) + (7 * 24 + 24) + (7 * 24 * 24 + 24) \
        + (24 + 24) + 2 * 160 * 24
    assert flops.block_bytes(160, 1, 24, 7, 1) == 4 * elems0
    # lanes scale the per-lane rows and outputs, not the weights
    assert flops.block_bytes(160, 24, 24, 7, 2048, lanes=64) == 4 * (
        64 * rows * 48 + (7 * 24 * 24 + 24) * 2 + 64 * 2 * 160 * 24)


def test_block_flops_and_chunk_counts():
    assert flops.block_flops(160, 24, 24, 7, lanes=64) == \
        2 * 64 * 160 * (2 * 7 * 24 * 24)
    counts = flops.chunk_kernel_counts(AUDIO, 160, 64)
    assert len(counts) == 12
    assert sum(f for f, _ in counts) == 2 * 64 * 160 * (4224 + 11 * 8064)


def test_least_time_names_the_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    t, bound = flops.least_time([(1000, 10), (100, 50)], peak)
    assert t == pytest.approx(10.0 + 5.0)
    assert bound == "compute"
    t, bound = flops.least_time([(100, 80)], peak)
    assert (t, bound) == (pytest.approx(8.0), "memory")


def test_peaks_by_device_kind():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "source" in p
    with pytest.raises(ValueError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        flops.peaks("cpu")


def test_kernel_op_pattern():
    kernel = ('%_scan_fused.12 = (f32[64,160,24]{2,1,0}, f32[64,160,24]'
              '{2,1,0}) custom-call(f32[64,166,1]{2,1,0} %x), '
              'custom_call_target="tpu_custom_call"')
    assert flops.TCN_BLOCK_OPS.search(kernel)
    assert not flops.TCN_BLOCK_OPS.search(
        '%custom-call.222 = f32[64,6144,24]{1,2,0} custom-call(), '
        'custom_call_target="AllocateBuffer"')
    assert not flops.TCN_BLOCK_OPS.search(
        '%fusion.108 = f32[786432,24]{0,1} fusion(%ring1)')
    assert flops.kernel_calls(AUDIO, [{}] * 46, 1) == 552
