"""Per-layer readers report nothing (None), never 0, when the trace holds
nothing for them, and read their numbers from the traced stretch."""
import json
import os

import pytest

from chipbench import flops, peaks
from chipbench.run import load_module
from chipbench.tests.conftest import REPO


def ctx(ops, *, busy_s=0.0, window_s=1.0, segments=4):
    with open(os.path.join(REPO, "chipbench", "configs",
                           "fedgs-cifar10.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(REPO, "chipbench", "traffic",
                           "table1-static.json")) as f:
        traffic = json.load(f)
    return {"ops": ops, "busy_s": busy_s, "window_s": window_s,
            "segments": segments, "seg": 5, "cells": 21, "cfg": cfg,
            "traffic": traffic, "chips": 1, "flops": flops,
            "peaks": peaks.chip_peaks("TPU v5 lite"), "n_val": 500}


def reader(name):
    return load_module(os.path.join(REPO, "chipbench", "metrics",
                                    f"{name}.py"), f"m_{name}")


@pytest.mark.parametrize("name", ["device_idle_share", "step_mfu"])
def test_reader_is_silent_without_a_stretch(name):
    assert reader(name).read(ctx([["fusion.1", 0.0, 1e6]],
                                 segments=0)) is None


def test_idle_share():
    c = ctx([["fusion.1", 0.0, 1e6]], busy_s=0.25, window_s=1.0)
    assert reader("device_idle_share").read(c) == pytest.approx(75.0)
    assert reader("device_idle_share").read(dict(c, ops=[])) is None


def test_step_mfu_is_the_stretchs_model_flops_over_peak():
    c = ctx([["fusion.1", 0.0, 1e6]], busy_s=2.0, window_s=2.5)
    f = flops.model_flops_per_cell_round(c["cfg"], c["traffic"], 500)
    # 4 segments of 5 rounds of 21 cells in 2.5 s on one 197 TFLOP/s chip
    assert reader("step_mfu").read(c) == pytest.approx(
        100 * 4 * 5 * 21 * f / 2.5 / 197e12)
    assert reader("step_mfu").read(dict(c, peaks=None)) is None
