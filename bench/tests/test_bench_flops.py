"""Operation and byte counts against hand counts at both configurations'
shapes, and the table of peaks."""
import json
from pathlib import Path

import pytest

from bench import flops
from bench.peaks import peak

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# 2 FLOPs per weight of each matmul a token passes (8 layers and the head),
# plus q.k and p.v over n tokens: 4 * H * hd * n per layer
@pytest.mark.parametrize("name, n, hand", [
    # MHA: per layer 4 * 4096^2 + 3 * 4096 * 11008 = 202,375,168 weights;
    # 8 layers + head 4096 * 102400 = 2,038,431,744 weights
    ("deepseek-7b-l8", 513, 2 * 2_038_431_744 + 8 * 4 * 4096 * 513),
    ("deepseek-7b-l8", 144, 2 * 2_038_431_744 + 8 * 4 * 4096 * 144),
    # GQA: per layer 2 * 4096^2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    # = 173,015,040; 8 layers + head 4096 * 64000 = 1,646,264,320 weights
    ("yi-9b-l8", 513, 2 * 1_646_264_320 + 8 * 4 * 4096 * 513),
    ("yi-9b-l8", 640, 2 * 1_646_264_320 + 8 * 4 * 4096 * 640),
])
def test_decode_step_flops(name, n, hand):
    assert flops.decode_step_flops(_config(name), n) == hand


@pytest.mark.parametrize("name, n, hand_flops, hand_bytes", [
    # K and V of 640 tokens: 2 * 640 * 32 heads * 128 * 2 B; q and o: 2 *
    # 32 * 128 * 2 B
    ("deepseek-7b-l8", 640, 4 * 32 * 128 * 640, 2 * 640 * 32 * 128 * 2 + 16_384),
    ("yi-9b-l8", 640, 4 * 32 * 128 * 640, 2 * 640 * 4 * 128 * 2 + 16_384),
    ("deepseek-7b-l8", 129, 4 * 32 * 128 * 129, 2 * 129 * 32 * 128 * 2 + 16_384),
])
def test_paged_attention_counts(name, n, hand_flops, hand_bytes):
    c = _config(name)
    assert flops.paged_attn_flops(c, n) == hand_flops
    assert flops.paged_attn_bytes(c, n) == hand_bytes


def test_paged_attention_is_memory_bound_on_v5e():
    c, p = _config("deepseek-7b-l8"), peak("TPU v5 lite")
    t, bound = flops.least_seconds(flops.paged_attn_flops(c, 640),
                                   flops.paged_attn_bytes(c, 640), p)
    assert bound == "memory"
    assert t == pytest.approx(10_502_144 / 819e9)


def test_v5e_peaks():
    p = peak("TPU v5 lite")
    assert (p.bf16_flops, p.hbm_bytes_per_s) == (197e12, 819e9)


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        peak(kind)
