"""The per-leaf path that packed program outputs replaced, kept as a test
reference: the report and explain programs with no packing, each field
fetched on its own and flattened on the host, then built into replies by
the same ``Session`` code.  Packing copies values, so replies built this
way must equal the served ones bit for bit."""
from __future__ import annotations

import jax
import numpy as np

from repro import api


def _per_leaf_arrays(perfs, extras) -> dict:
    """What a report reads, fetched one field at a time from the unpacked
    outputs: the path the packed buffers replace."""
    state = perfs.state
    fields = ("runtime", "energy", "power", "edp", "cycles", "energy_mem",
              "energy_comp", "energy_leak", "area")
    return dict(
        {k: np.asarray(getattr(state, k)) for k in ("reads", "writes", "comp_ops", "bw_util")},
        extras={k: np.asarray(v) for k, v in extras.items()},
        **{k: np.asarray(getattr(perfs, k)) for k in fields},
    )


def _per_leaf_elasticities(g_tech, g_arch) -> np.ndarray:
    return np.concatenate(
        [np.atleast_1d(np.asarray(x)) for x in jax.tree.leaves((g_tech, g_arch))]
    )


def _unpacked_programs(sess, spec, objective="edp"):
    """The report and explain programs with no packing, one request and a
    request axis."""
    mcfg = sess.mcfg

    def report(t, a, g):
        return api._report_lane(t, a, g, spec, mcfg)

    def explain(t, a, g):
        return api._explain_lane(t, a, g, spec, mcfg, objective)

    return (jax.jit(report), jax.jit(explain),
            jax.jit(jax.vmap(report)), jax.jit(jax.vmap(explain)))


def per_leaf_replies(sess, workloads, architectures, *, kind, objective="edp",
                     request_bucket=None):
    """Replies built the per-leaf way from the unpacked programs: one
    request at a time with ``request_bucket`` None, else one batch."""
    ws = [sess._workload(w) for w in workloads]
    archs = [sess._arch(a) for a in architectures]
    report, explain, b_report, b_explain = _unpacked_programs(sess, archs[0].spec, objective)
    if request_bucket is None:
        args = [(a.tech, a.arch, w.stacked) for w, a in zip(ws, archs)]
        reports = [report(*x) for x in args]
        grads = [explain(*x) for x in args] if kind == "explain" else None
    else:
        _, _, _, stacked = sess._assemble_batch(ws, archs, request_bucket)

        def lanes(tree):
            return [jax.tree.map(lambda x: x[i], tree) for i in range(len(ws))]

        reports = lanes(b_report(*stacked))
        grads = lanes(b_explain(*stacked)) if kind == "explain" else None
    replies = []
    for i, (w, a) in enumerate(zip(ws, archs)):
        rep = sess._build_report(a, w, _per_leaf_arrays(*reports[i]))
        if kind == "explain":
            rep = api._attributed(rep, objective, _per_leaf_elasticities(*grads[i]))
        replies.append(rep)
    return replies
