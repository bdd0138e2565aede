"""The ``.dhd`` library designs that seed a frontier's population are the
configurations' design numbers, as the program holds them (float32): the
reference seeds its population from the configuration files, the program
from the library."""
import json

import numpy as np
import pytest

from conftest import CHIP, HERE

CONFIGS = [CHIP / "configs" / "lm_1024.json", CHIP / "configs" / "mlperf_small.json",
           HERE / "data" / "configs" / "frontier_small.json"]
DESIGNS = [(path, name) for path in CONFIGS for name in json.loads(path.read_text())["architectures"]]


@pytest.mark.parametrize("path,name", DESIGNS, ids=[f"{p.stem}-{n}" for p, n in DESIGNS])
def test_library_design_is_the_config(path, name):
    from repro.core.dhdl import load_arch

    want = json.loads(path.read_text())["architectures"][name]
    ca = load_arch(name)
    assert list(ca.spec.mem_type) == want["mem_type"]
    for side, tree in (("tech", ca.tech), ("arch", ca.arch)):
        got = {k: np.asarray(v) for k, v in vars(tree).items()}
        assert set(got) == set(want[side])
        for k, v in want[side].items():
            np.testing.assert_array_equal(got[k], np.asarray(v, np.float32), err_msg=f"{side}.{k}")
