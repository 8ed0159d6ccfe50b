"""Operation and parameter counts against hand counts."""
import json
import os

from chipbench import flops
from chipbench.tests.conftest import REPO


def paper_cfg():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "fedgs-cifar10.json")) as f:
        return json.load(f)


def test_cnn_forward_macs_per_sample():
    # 32x32x3 image, two VALID 5x5 convolutions of 64 with 2x2 pools:
    # conv0 28*28 positions * 5*5*3 taps * 64 = 3,763,200; conv1 on the
    # pooled 14x14x64: 10*10 * 5*5*64 * 64 = 10,240,000; dense from the
    # pooled 5*5*64 = 1,600 to 384: 614,400; 384 -> 192: 73,728;
    # 192 -> 10: 1,920
    assert flops.cnn_macs_per_sample(paper_cfg()) == \
        3_763_200 + 10_240_000 + 614_400 + 73_728 + 1_920


def test_param_count_is_the_cifar_cnn():
    # 4,800 + 64 + 102,400 + 64 + 614,400 + 384 + 73,728 + 192 + 1,920 + 10
    assert flops.param_count(paper_cfg()) == 797_962


def test_model_flops_per_cell_round_paper_setting():
    f = 2 * 14_693_248
    # 75 eval rounds (t even) plus the last round, over 500 held-out
    # samples; no init is counted
    want = 10 * 10 * 32 * 3 * f + 76 / 150 * 500 * f
    got = flops.model_flops_per_cell_round(
        paper_cfg(), {"graph": {"kind": "oracle_static"}}, 500)
    assert abs(got - want) <= 1e-9 * want
