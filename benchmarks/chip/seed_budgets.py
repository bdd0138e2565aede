#!/usr/bin/env python3
"""Per-graph budgets and hypervolume box of a frontier mix, from its seed
designs: what the mix's ``per_graph`` entry holds.

    python3 benchmarks/chip/seed_budgets.py lm_1024 frontier

For each graph of the configuration, the seed designs the mix's frontier
call names are priced by the plain reference (float64, on the host).  The
area budget and the power budget are the largest area and power among
them: every seed starts feasible, and mixes that grow a design run into a
binding ceiling.  The box is fixed from the seeds' log metrics (the call's
``metrics``, in order): ``lo`` leaves e^3 of room below the best seed on
each axis, ``ref`` lies 0.5 beyond the worst, so hypervolumes of calls on
one graph compare.  Numbers are rounded to float32, as the program takes
them.  Prints the ``per_graph`` object as JSON.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

LOG_KEYS = {"time": "runtime_s", "energy": "energy_j", "area": "area_mm2", "edp": "edp"}


def per_graph(config: dict, call: dict) -> dict:
    """The ``per_graph`` entry for ``call`` (a frontier call's arguments);
    the caller has switched JAX to float64."""
    import check as C

    inputs = C.Inputs(config)
    f32 = lambda x: float(np.float32(x))
    out = {}
    for g in inputs.graphs:
        reps = [C.reference_report(inputs, SimpleNamespace(graph=g, design=C.Library(s)))
                for s in call["seeds"]]
        logm = np.array([[np.log(float(r[LOG_KEYS[m]])) for m in call["metrics"]] for r in reps])
        out[g] = {"area_budget": f32(max(float(r["area_mm2"]) for r in reps)),
                  "power_budget": f32(max(float(r["power_w"]) for r in reps)),
                  "hv_box": [[f32(v) for v in logm.min(axis=0) - 3.0],
                             [f32(v) for v in logm.max(axis=0) + 0.5]]}
    return out


def main(argv=None) -> int:
    import jax

    jax.config.update("jax_enable_x64", True)
    config_name, traffic = (argv or sys.argv[1:])[:2]
    config = json.loads((HERE / "configs" / f"{config_name}.json").read_text())
    config["dir"] = str(HERE)
    mix = json.loads((HERE / "traffic" / f"{traffic}.json").read_text())
    print(json.dumps(per_graph(config, mix["call"]["frontier"]), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
