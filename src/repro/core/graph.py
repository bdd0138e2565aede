"""Workload dataflow graphs (paper §4).

A workload is a DAG of operator vertices.  For JAX-friendliness the graph is
a struct-of-arrays: per-vertex resource stats (the paper's "vertex state"
inputs: compute ops per compute class, bytes read/written/allocated per
memory unit) plus matmul-ish dims for utilization modelling and an op-kind
tag.  Edges are kept for the graph-level compiler passes (compute-merge,
bridge partitioning — paper Alg. 3); the mapper consumes vertices in
topological order, as the paper's MAPWORKLOAD does after workloadOptimize.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.params import COMP_IDX, MEM_IDX, N_COMP, N_MEM

# op kinds
MATMUL, ELEMWISE, REDUCTION, SCAN, GATHER, SOFTMAX, CONV, MISC = range(8)
KIND_NAMES = ("matmul", "elemwise", "reduction", "scan", "gather", "softmax", "conv", "misc")

# routing of op kinds onto compute classes (fractions of the op's FLOPs):
#                         sysArr vector macTree fpu
_KIND_ROUTE = np.array(
    [
        [1.00, 0.00, 0.00, 0.00],  # matmul  -> systolic array
        [0.00, 1.00, 0.00, 0.00],  # elemwise-> vector
        [0.00, 0.20, 0.80, 0.00],  # reduction -> mac tree (+ vector epilogue)
        [0.00, 0.90, 0.00, 0.10],  # scan    -> vector w/ fpu control
        [0.00, 0.50, 0.00, 0.50],  # gather  -> address calc on fpu
        [0.00, 0.60, 0.40, 0.00],  # softmax -> vector exp + tree reductions
        [1.00, 0.00, 0.00, 0.00],  # conv    -> systolic array
        [0.00, 0.00, 0.00, 1.00],  # misc    -> fpu
    ],
    np.float32,
)


@dataclass
class Graph:
    """Struct-of-arrays DFG.  All data arrays have leading dim V."""

    n_comp: jax.Array  # [V, N_COMP] FLOPs routed per compute class
    n_read: jax.Array  # [V, N_MEM]  bytes read
    n_write: jax.Array  # [V, N_MEM]  bytes written
    n_alloc: jax.Array  # [V, N_MEM]  bytes that must be resident (working set)
    dims: jax.Array  # [V, 3]  (M, N, K) for utilization modelling
    op_kind: jax.Array  # [V] int32
    edges: jax.Array  # [E, 2] int32 (src, dst)
    names: tuple = field(default=())  # static metadata

    @property
    def n_vertices(self) -> int:
        return self.n_comp.shape[0]

    @property
    def total_flops(self) -> jax.Array:
        return jnp.sum(self.n_comp)

    def pad_to(self, v: int) -> "Graph":
        """Pad vertex arrays to ``v`` (no-op vertices) for batched DSE."""
        cur = self.n_comp.shape[0]
        if cur == v:
            return self
        assert cur < v, (cur, v)
        p = v - cur

        def pad(x):
            cfg = [(0, p)] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, cfg)

        return Graph(
            n_comp=pad(self.n_comp),
            n_read=pad(self.n_read),
            n_write=pad(self.n_write),
            n_alloc=pad(self.n_alloc),
            dims=pad(self.dims),
            op_kind=pad(self.op_kind),
            edges=self.edges,
            names=self.names + ("pad",) * p,
        )

    @staticmethod
    def stack(graphs: "list[Graph]") -> "Graph":
        """Stack workloads into one Graph with a leading workload axis W.

        Every data array becomes [W, V_max, ...] (vertex lists padded with
        no-op vertices via :meth:`pad_to`; the mapper prices no-op vertices
        at zero cycles and excludes them from the tile/memory-time
        diagnostics, so padding is exact for the whole MapState).  This is the batched-workload
        convention shared by DOpt's multi-workload loss and popsim's
        population DSE: simulate is vmapped over the leading axis.  Edges are
        ragged across workloads and unused by the mapper, so the stacked
        graph carries an empty edge list.
        """
        assert graphs, "Graph.stack needs at least one graph"
        vmax = max(g.n_vertices for g in graphs)
        gs = [g.pad_to(vmax) for g in graphs]
        stk = lambda f: jnp.stack([getattr(g, f) for g in gs])
        return Graph(
            n_comp=stk("n_comp"),
            n_read=stk("n_read"),
            n_write=stk("n_write"),
            n_alloc=stk("n_alloc"),
            dims=stk("dims"),
            op_kind=stk("op_kind"),
            edges=jnp.zeros((len(gs), 0, 2), jnp.int32),
            names=tuple(g.names for g in gs),
        )


jax.tree_util.register_dataclass(
    Graph,
    data_fields=["n_comp", "n_read", "n_write", "n_alloc", "dims", "op_kind", "edges"],
    meta_fields=["names"],
)


class GraphBuilder:
    """Imperative construction (numpy), immutable Graph output."""

    def __init__(self):
        self._rows: list[dict] = []
        self._edges: list[tuple[int, int]] = []
        self._last: int | None = None

    def __len__(self) -> int:
        return len(self._rows)

    def add(
        self,
        name: str,
        kind: int,
        flops: float,
        *,
        gbuf_read: float = 0.0,
        gbuf_write: float = 0.0,
        main_read: float = 0.0,
        main_write: float = 0.0,
        alloc: float = 0.0,
        dims: tuple[float, float, float] = (1.0, 1.0, 1.0),
        deps: list[int] | None = None,
        chain: bool = True,
    ) -> int:
        """Add a vertex; returns its index.

        ``alloc`` is the on-chip working set (globalBuf).  localMem traffic is
        modelled as operand/register traffic proportional to FLOPs.
        """
        vid = len(self._rows)
        local = flops * 1.0  # ~1 byte of register-file traffic per FLOP
        n_read = np.zeros(N_MEM, np.float32)
        n_write = np.zeros(N_MEM, np.float32)
        n_alloc = np.zeros(N_MEM, np.float32)
        n_read[MEM_IDX["localMem"]] = local
        n_write[MEM_IDX["localMem"]] = local * 0.5
        n_read[MEM_IDX["globalBuf"]] = gbuf_read
        n_write[MEM_IDX["globalBuf"]] = gbuf_write
        n_read[MEM_IDX["mainMem"]] = main_read
        n_write[MEM_IDX["mainMem"]] = main_write
        n_alloc[MEM_IDX["globalBuf"]] = alloc
        n_alloc[MEM_IDX["mainMem"]] = main_read + main_write
        self._rows.append(
            dict(
                name=name,
                kind=kind,
                n_comp=_KIND_ROUTE[kind] * np.float32(flops),
                n_read=n_read,
                n_write=n_write,
                n_alloc=n_alloc,
                dims=np.asarray(dims, np.float32),
            )
        )
        if deps is not None:
            for d in deps:
                self._edges.append((d, vid))
        elif chain and self._last is not None:
            self._edges.append((self._last, vid))
        self._last = vid
        return vid

    def build(self) -> Graph:
        assert self._rows, "empty graph"
        return Graph(
            n_comp=jnp.asarray(np.stack([r["n_comp"] for r in self._rows])),
            n_read=jnp.asarray(np.stack([r["n_read"] for r in self._rows])),
            n_write=jnp.asarray(np.stack([r["n_write"] for r in self._rows])),
            n_alloc=jnp.asarray(np.stack([r["n_alloc"] for r in self._rows])),
            dims=jnp.asarray(np.stack([r["dims"] for r in self._rows])),
            op_kind=jnp.asarray(np.array([r["kind"] for r in self._rows], np.int32)),
            edges=jnp.asarray(
                np.array(self._edges, np.int32).reshape(-1, 2)
                if self._edges
                else np.zeros((0, 2), np.int32)
            ),
            names=tuple(r["name"] for r in self._rows),
        )


# --------------------------------------------------------------------------- #
# Graph-level compiler passes (paper Alg. 3: workloadOptimize)
# --------------------------------------------------------------------------- #


def compute_merge(g: Graph, flops_threshold: float = 1e6) -> Graph:
    """Compute Merge Optimizer (paper Alg. 3): greedily merge consecutive
    small vertices (all below threshold) into one, summing their stats.
    Operates on the topological order; preserves total work exactly."""
    nc = np.asarray(g.n_comp)
    small = nc.sum(-1) < flops_threshold
    rows = []
    group: list[int] = []
    order = list(range(g.n_vertices))

    def flush():
        if group:
            rows.append(list(group))
            group.clear()

    for v in order:
        if small[v]:
            group.append(v)
            if sum(nc[group].sum(-1)) >= flops_threshold:
                flush()
        else:
            flush()
            rows.append([v])
    flush()

    def merge(x):
        x = np.asarray(x)
        return jnp.asarray(np.stack([x[idx].sum(0) for idx in rows]))

    dims = np.asarray(g.dims)
    kind = np.asarray(g.op_kind)
    return Graph(
        n_comp=merge(g.n_comp),
        n_read=merge(g.n_read),
        n_write=merge(g.n_write),
        n_alloc=jnp.asarray(
            np.stack([np.asarray(g.n_alloc)[idx].max(0) for idx in rows])
        ),
        dims=jnp.asarray(np.stack([dims[idx[0]] for idx in rows])),
        op_kind=jnp.asarray(np.array([kind[idx[0]] for idx in rows], np.int32)),
        edges=jnp.zeros((0, 2), jnp.int32),
        names=tuple("+".join(g.names[i] for i in idx) if len(idx) > 1 else g.names[idx[0]] for idx in rows),
    )


def workload_optimize(g: Graph, merge_threshold: float = 0.0) -> Graph:
    """paper §5.2 workloadOptimize: DFG partitioning + compute merge.
    The struct-of-arrays graph is already topologically ordered by
    construction; optionally merge small vertices."""
    if merge_threshold > 0:
        g = compute_merge(g, merge_threshold)
    return g
