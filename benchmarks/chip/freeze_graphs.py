#!/usr/bin/env python3
"""Write the benchmark's workload graphs, the data every query prices, to
``graphs/<file>`` as the configurations name them.

    PYTHONPATH=src python3 benchmarks/chip/freeze_graphs.py [--check]

Each graph is built once by the builder its configuration entry names
(``builder``, ``args``: the repo's tracer and MLPerf builders) and kept as
plain arrays.  Runs give the program and the reference these files, so
neither takes graphs that the program makes at run time, and a later change
to the builders does not move the benchmark.  ``--check`` only compares the
files with what the builders give today and exits 1 where they differ.
"""
from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def built(entry: dict) -> dict:
    from repro import workloads as W

    g = getattr(W, entry["builder"])(*entry["args"])
    out = {f.name: np.asarray(getattr(g, f.name)) for f in dataclasses.fields(g) if f.name != "names"}
    out["names"] = np.asarray(g.names, dtype=str)
    return out


def main(argv=None) -> int:
    check = "--check" in (argv if argv is not None else sys.argv[1:])
    differ = 0
    for path in sorted((HERE / "configs").glob("*.json")):
        for entry in json.loads(path.read_text())["graphs"]:
            target = HERE / entry["file"]
            arrays = built(entry)
            if check:
                with np.load(target) as kept:
                    same = set(kept.files) == set(arrays) and all(
                        kept[k].dtype == v.dtype and np.array_equal(kept[k], v) for k, v in arrays.items())
                print(f"{entry['name']}: {'same' if same else 'DIFFERS'}")
                differ += not same
            else:
                target.parent.mkdir(parents=True, exist_ok=True)
                np.savez_compressed(target, **arrays)
                print(f"{entry['name']}: {len(arrays['names'])} vertices -> {target.relative_to(HERE)}")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
