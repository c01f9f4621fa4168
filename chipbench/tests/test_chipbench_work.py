"""Required-work counters against sums worked by hand."""
import json
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

from chipbench import spec  # noqa: E402

SMOLLM = json.loads((REPO / "chipbench/configs/review-smollm135m.json").read_text())


def test_smollm_counters():
    m = spec.deployment_module("review-smollm135m")
    # per layer: q 576*576 + k,v 2*576*192 + o 576*576 + MLP 3*576*1536
    per_layer = 331_776 + 221_184 + 331_776 + 2_654_208
    assert per_layer == 3_538_944
    # 30 layers + tied head 576*49152, two FLOPs a weight
    assert m.dense_flops_per_token(SMOLLM) == 2 * (30 * 3_538_944 + 28_311_552) == 268_959_744
    n = np.array([1, 31])
    # 4 * head_dim 64 * 9 heads * 30 layers * n(n+1)/2 causal pairs
    assert list(m.attention_flops(SMOLLM, n)) == [69_120, 69_120 * 496]
    # q and o: 9*64 each, k and v: 3*64 each, bf16, 30 layers
    assert list(m.attention_bytes(SMOLLM, n)) == [2 * 30 * 1536, 2 * 30 * 31 * 1536]


def test_hsv_counters():
    m = spec.deployment_module("uc1-lostdog")
    px = 2 * 224 * 224
    assert m.hsv_work(2, 224) == (134.0 * px, 12.0 * px)
    assert m.hsv_work(2, 224) == (13_447_168.0, 1_204_224.0)
