"""The control -- the plain reference computed in bfloat16, one step below
the configuration's float32, put in the program's place -- fails the limits
that decide ``correct``, here at the MLPerf graphs' own sizes on the CPU."""
import json
from pathlib import Path

import pytest

import check as C
import generator as G

CHIP = Path(C.__file__).resolve().parent
CONFIG = json.loads((CHIP / "configs" / "mlperf_small.json").read_text())
# the limits of the cells that send these queries at these sizes
SWEEP = json.loads((CHIP / "traffic" / "notebook_sweep.json").read_text())["check"]["limits"]
DOPT_MIX = json.loads((CHIP / "traffic" / "optimize.json").read_text())
DOPT = DOPT_MIX["check"]


@pytest.fixture(scope="module")
def inputs(x64):
    return C.Inputs(dict(CONFIG, dir=str(CHIP)))


@pytest.mark.parametrize("graph", [g["name"] for g in CONFIG["graphs"]])
def test_control_fails_report_limits(inputs, graph):
    q = G.Query(0, "simulate", graph, G.Design("edge"))
    ok, checks = C.judge(C.control_readings(inputs, [q]), SWEEP)
    assert not ok, checks


@pytest.fixture(scope="module")
def dopt_control(inputs):
    q = G.Query(0, "optimize", "bert_large", G.Design("edge"), call=G.call_of(DOPT_MIX, "optimize"))
    return C.control_readings(inputs, [q])


def test_control_fails_history_limit(dopt_control):
    assert dopt_control["history_gap"] > DOPT["limits"]["history_gap"], dopt_control


def test_control_fails_design_limit(dopt_control):
    assert dopt_control["design_gap"] > DOPT["limits"]["design_gap"], dopt_control


@pytest.mark.parametrize("graph", [g["name"] for g in CONFIG["graphs"]])
def test_control_fails_elasticity_limit(inputs, graph):
    q = G.Query(0, "explain", graph, G.Design("edge"), call={"objective": "edp"})
    readings = C.control_readings(inputs, [q])
    assert readings["elasticity_gap"] > SWEEP["elasticity_gap"], readings


FRONTIER_MIX = json.loads((CHIP / "traffic" / "frontier.json").read_text())
LM = json.loads((CHIP / "configs" / "lm_1024.json").read_text())


@pytest.mark.parametrize("graph", [g["name"] for g in LM["graphs"]])
def test_control_fails_frontier_limits(x64, graph):
    """The frontier cell's own call, at its population, on each of its
    graphs: the members the check follows, descended in bfloat16."""
    inputs = C.Inputs(dict(LM, dir=str(CHIP)))
    call = G.call_for(FRONTIER_MIX, "frontier", graph, FRONTIER_MIX["keys"][0])
    q = G.Query(0, "frontier", graph, G.Design("base"), call=call)
    ok, checks = C.judge(C.control_readings(inputs, [q]), FRONTIER_MIX["check"]["limits"])
    assert not ok, checks
