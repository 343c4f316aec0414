"""Operation and byte counts against hand counts at both configurations."""
import json

import pytest

from chipbench import manifest, opcount


def config(name):
    return json.loads((manifest.BENCH_DIR / "configs" / f"{name}.json")
                      .read_text())


# (config, per-layer conv flops, head flops, frame bytes, feature shape):
# 2 * out_h * out_w * k * k * c_in * c_out per layer, real c_in, SAME
# outputs ceil(size / 2); head 2 * (h * w * k) * 512
HAND = [
    ("mc4-84-c9", [2 * 42 * 42 * 16 * 9 * 16, 2 * 21 * 21 * 9 * 16 * 16,
                   2 * 11 * 11 * 9 * 16 * 4], 2 * 484 * 512,
     84 * 84 * 9 * 4, (11, 11, 4)),
    ("mc4-256-c4", [2 * 128 * 128 * 16 * 4 * 16, 2 * 64 * 64 * 9 * 16 * 16,
                    2 * 32 * 32 * 9 * 16 * 4], 2 * 4096 * 512,
     256 * 256 * 4 * 4, (32, 32, 4)),
]


@pytest.mark.parametrize("name,convs,head,frame,shape", HAND)
def test_counts_match_hand_counts(name, convs, head, frame, shape):
    cfg = config(name)
    assert [l.flops for l in opcount.layers(cfg)] == convs
    assert opcount.head_flops_per_frame(cfg) == head
    assert opcount.flops_per_frame(cfg) == sum(convs) + head
    assert opcount.feature_shape(cfg) == shape
    assert opcount.frame_bytes(cfg) == frame
    weights = 4 * (sum(l.weights for l in opcount.layers(cfg))
                   + (shape[0] * shape[1] * shape[2] + 1) * 512)
    assert opcount.bytes_per_launch(cfg, 8) == 8 * frame + weights + 8 * 512 * 4


def test_standard_encoder_is_10_8_mflop_per_frame():
    assert opcount.flops_per_frame(config("mc4-84-c9")) == 10_795_648


@pytest.mark.parametrize("name", ["mc4-84-c9", "mc4-256-c4"])
def test_roofline_is_memory_bound_on_v5e(name):
    peaks = json.loads((manifest.BENCH_DIR / "peaks.json").read_text())
    least, bound = opcount.roofline_s(config(name), 8, peaks["TPU v5 lite"])
    assert bound == "memory"
    cfg = config(name)
    assert least == pytest.approx(opcount.bytes_per_launch(cfg, 8) / 819e9)
