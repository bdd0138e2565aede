"""The graph files hold what each configuration says of its graphs: the
vertex count, arrays that fit the configuration's bucket, and finite,
non-negative counts."""
import json
from pathlib import Path

import numpy as np
import pytest

import check as C

CHIP = Path(C.__file__).resolve().parent
CONFIGS = [json.loads(p.read_text()) for p in sorted((CHIP / "configs").glob("*.json"))]


@pytest.mark.parametrize("config", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_graph_files_match_configuration(config):
    graphs = C.load_graphs(dict(config, dir=str(CHIP)))
    for entry in config["graphs"]:
        g = graphs[entry["name"]]
        v = entry["vertices"]
        assert len(g["names"]) == v <= entry["bucket"]
        assert g["n_comp"].shape == (v, 4)
        for k in ("n_read", "n_write", "n_alloc", "dims"):
            assert g[k].shape == (v, 3)
        for k in ("n_comp", "n_read", "n_write", "n_alloc"):
            assert np.all(np.isfinite(g[k])) and np.all(g[k] >= 0)
        assert g["edges"].shape == (v - 1, 2) or g["edges"].shape[1] == 2
