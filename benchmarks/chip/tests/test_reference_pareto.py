"""The reference's population DSE pieces against the program's on the same
inputs: the start points and objective mixes one key gives, the front over
one set of points, and its hypervolume.  The program's side runs in
float32 (x64 off), as it is served; the reference's in float64."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check as C
import reference as R
from conftest import CHIP

LM = json.loads((CHIP / "configs" / "lm_1024.json").read_text())
SEEDS = ("base", "edge", "datacenter")


@pytest.fixture
def x32():
    """Program side with x64 off; the test switches it on for the
    reference and the fixture puts the flag back."""
    before = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    yield
    jax.config.update("jax_enable_x64", before)


def _points(n, m, seed):
    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(n, m)).astype(np.float32)
    pts[5] = pts[3]  # a duplicate: neither dominates the other
    pts[7, 0] = pts[2, 0]  # a tie on one axis
    feasible = rng.random(n) < 0.8
    return pts, feasible


@pytest.mark.parametrize("m", [2, 3])
def test_front_and_hypervolume_match(x32, m):
    from repro.core import pareto

    pts, feasible = _points(300, m, 11 + m)
    want = np.asarray(pareto.non_dominated_mask(jnp.asarray(pts), jnp.asarray(feasible)))
    front = pts[want]
    lo, ref = pts.min(axis=0) - 0.5, pts.max(axis=0) + 0.5
    hv = float(pareto.hypervolume(jnp.asarray(front), jnp.asarray(ref), lo=jnp.asarray(lo)))
    jax.config.update("jax_enable_x64", True)
    got = R.non_dominated(pts.astype(np.float64), feasible)
    np.testing.assert_array_equal(got, want)
    least, most = R.hypervolume(front, lo, ref, R.TIE_REL)
    assert least <= most and least == pytest.approx(hv, rel=1e-5) and most == pytest.approx(hv, rel=1e-5)
    assert R.hypervolume(front, lo, ref) == pytest.approx((hv, hv), rel=1e-5)


def test_population_start_and_mixes_match(x32):
    from repro import api

    popsim = api._popsim  # the population engine the façade drives
    key = jax.random.PRNGKey(2**31 - 99)
    k_seed, k_mix = jax.random.split(key)
    (tech, arch), _, names = popsim.seed_population(40, SEEDS, k_seed, 0.25)
    mixes = np.asarray(popsim.sample_objective_mixes(40, ("time", "energy", "area"), k_mix, 0.7))
    prog = {f"tech.{k}": np.asarray(v) for k, v in vars(tech).items()}
    prog.update({f"arch.{k}": np.asarray(v) for k, v in vars(arch).items()})
    jax.config.update("jax_enable_x64", True)
    inputs = C.Inputs(dict(LM, dir=str(CHIP)))
    designs = [inputs.design(C.Library(s))[:2] for s in SEEDS]
    rt, ra = R.seed_population(designs, 40, k_seed, 0.25, jnp.float64)
    for side, tree in (("tech", rt), ("arch", ra)):
        for k, v in tree.items():
            p = prog[f"{side}.{k}"]
            np.testing.assert_array_equal(p[:3], np.asarray(v, np.float32)[:3], err_msg=k)  # the designs
            np.testing.assert_allclose(p, np.asarray(v), rtol=2e-6, err_msg=k)
    np.testing.assert_allclose(R.sample_objective_mixes(40, ("time", "energy", "area"), k_mix, 0.7),
                               mixes, rtol=0, atol=0)
