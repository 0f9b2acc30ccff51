"""The bfloat16 control: the plain reference computed one precision
below the float32 the configurations state, put in the program's place,
reads above each cell's limit; the program reads below it."""
import pytest

import jax.numpy as jnp

from bench import run
from bench.spec import Bench
from bench.tests.tiny import make_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", ["canny-m-1080p.backlog",
                                      "tbackground-t-1080p.cams30"])
def test_control_fails_the_limit(root, monkeypatch, workload):
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "")
    cell = Bench(root).cell(workload)
    res, _ = run.measure(cell, 2**31 + 99, 0.5, False, on_chip=False,
                         controls=(jnp.bfloat16,))
    limit = res["compared"]["max_scale_ulp"]["limit"]
    assert res["compared"]["max_scale_ulp"]["value"] <= limit
    assert res["control"]["bfloat16"] > limit
