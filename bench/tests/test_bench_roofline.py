"""Least-bytes arithmetic and the peak table."""
import os

import pytest

from bench import peaks, roofline
from bench.spec import ROOT, load_json

CANNY = load_json(os.path.join(ROOT, "bench/configs/canny-m-1080p.json"))
TBG = load_json(os.path.join(ROOT, "bench/configs/tbackground-t-1080p.json"))
V5E = peaks.peaks_for("TPU v5 lite")


def test_frame_bytes():
    assert roofline.frame_bytes(CANNY) == 1920 * 1080 * 4 == 8_294_400


@pytest.mark.parametrize("config,frames,nbytes,micros", [
    (CANNY, 4, 66_355_200, 81.0),      # 4 in + 4 out
    (TBG, 4, 124_416_000, 151.9),      # 4 new + 7 history + 4 out
    (TBG, 1, 74_649_600, 91.1),        # 1 + 7 + 1
])
def test_least_bytes(config, frames, nbytes, micros):
    assert roofline.least_bytes(config, frames) == nbytes
    assert roofline.least_seconds(config, frames, V5E) * 1e6 == \
        pytest.approx(micros, abs=0.05)


def test_peaks_by_device_kind():
    assert V5E["hbm_bytes_per_s"] == 819e9
    assert V5E["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in V5E["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
