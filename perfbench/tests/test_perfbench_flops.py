"""FLOP and byte counts from shapes, against hand counts."""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
from benchlib.flops import (flash_fwd_cost, matmul_params,  # noqa: E402
                            roofline_seconds, train_flops_per_token)
from benchlib.model import from_doc  # noqa: E402


def model(name):
    return from_doc(json.loads((BENCH / "configs" / f"{name}.json")
                               .read_text()))


def test_granite_counts():
    m = model("granite-3-8b-l4")
    # 4 x (2 x 4096^2 + 2 x 4096 x 1024 + 3 x 4096 x 12800) + 4096 x 49155
    assert matmul_params(m) == 998_256_640
    # 6 x 998,256,640 + 6 x 4 x 4096 x 32 x 128
    assert train_flops_per_token(m, 4096) == 6_392_193_024
    assert train_flops_per_token(m, 2048) == pytest.approx(6.19e9, rel=1e-3)


def test_qwen_counts():
    m = model("qwen1.5-32b-l4")
    # 4 x (2 x 5120^2 + 2 x 5120 x 1024 + 3 x 5120 x 27392) + 5120 x 152064
    assert matmul_params(m) == 2_713_190_400
    assert train_flops_per_token(m, 4096) == 16_782_458_880


def test_flash_forward_cost():
    # one head of one sequence at 4096 x 128: 2 matmuls x 2 FLOPs x half
    # of 4096^2 pairs x 128; q, k, v, out at 2 bytes
    flops, bytes_ = flash_fwd_cost(1, 4096, 128)
    assert flops == 2 * 2 * 4096 * 4096 * 128 / 2
    assert bytes_ == 4 * 4096 * 128 * 2
    t, bound = roofline_seconds(32 * flops, 32 * bytes_, 197e12, 819e9)
    assert bound == "compute" and t == pytest.approx(32 * flops / 197e12)
    assert roofline_seconds(1.0, 1e9, 197e12, 819e9)[1] == "memory"
