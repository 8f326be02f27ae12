"""The benchmark's work arithmetic against the paper's published counts."""
import json

import pytest

import tinyroot
import work

X4 = {"channels": 54, "n_sfb": 5, "scale": 4, "bias": True, "in_channels": 3}
X2 = dict(X4, scale=2)


def test_c54_x4_macs_per_lr_pixel():
    assert work.macs_per_lr_pixel(X4, 54) == 52_326


@pytest.mark.parametrize("model,params", [(X4, 53_886), (X2, 51_906)])
def test_param_counts_match_the_paper(model, params):
    assert work.param_count(model) == params


@pytest.mark.parametrize("name", ["essr_x4_1080p", "essr_x2_2160p"])
def test_configs_state_the_published_counts(name):
    cfg = json.loads((tinyroot.BENCH / "configs" / f"{name}.json").read_text())
    assert work.param_count(cfg["model"]) == cfg["params_published"]


def test_frame_work_adds_up_by_lane():
    counts = (10, 20, 30)
    area = 32 * 32
    want = area * (10 * 4 * 3 * 16 + 20 * work.macs_per_lr_pixel(X4, 27)
                   + 30 * 52_326)
    assert work.frame_macs(X4, 32, counts) == want
    assert work.subnet_macs(X4, 32, counts) == want - area * 10 * 4 * 3 * 16
    assert work.subnet_bytes(X4, 32, counts) == 50 * area * 3 * 17 * 4


def test_least_time_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time_s(1000.0, 10.0, peaks) == (10.0, "compute")
    assert work.least_time_s(10.0, 1000.0, peaks) == (100.0, "memory")
