"""Level-synchronous schedule-DP sweeps as fused device kernels.

The batched evaluator (``repro.core.eval_batch``) and the device-resident
search engine (``repro.core.device_search``) spend their exact-evaluation
time in one recursion: the longest-path DP over the combined conjunctive
(DAG) + disjunctive (machine-order) graph, forward for start/finish times and
backward for the tails Q (Eq. 28).  The NumPy engine runs it as a dynamic
frontier with ``np.maximum.at`` scatters; the PR-2 JAX port kept the scatter
formulation and materialized every level's scatter/bincount on the host XLA
graph, which is why ``backend="jax"`` lost to NumPy on CPU.

This module reformulates the sweep *gather-side*: a task's start is the max
over its (dense-padded) predecessor slots of their finish times, and a task
is ready exactly when all those slots are done.  Per level that is one
gather, one masked max-reduce, and one masked update — no scatter, no
bincount — and the whole level loop lives in one compiled ``while_loop``:

* :func:`sweep_xla` — the pure-``jnp`` reference lowering.  It is the
  building block the device search engine jits/vmaps, and the default
  ``backend="jax"`` path on CPU/GPU.
* :func:`sweep_pallas` — the Pallas TPU kernel (``interpret=True`` runs the
  same kernel through the interpreter on CPU, used by the parity tests and
  the CI smoke leg).  It replaces the per-slot gather with masked reduces
  so the inner loop maps onto the VPU without dynamic vector gathers: one
  candidate row per grid step, predecessors reduced 128 lanes at a time
  over the DAG adjacency OR the machine-pred one-hot; the backward sweep
  does the same over the transposed adjacency and the machine successors.

Both implementations are **bit-exact** with the NumPy engine when run in
float64 (every reduction is a pure float max over the identical operand set,
and ``finish = start + dur`` uses the identical operands); on TPU (no f64)
they match to float32 tolerance.  Levels are identical too: the ready
frontier at loop step ``k`` is exactly the level-``k`` pop set of the Kahn
sweep.  Rows whose disjunctive graph is cyclic stall before completing and
come back with ``n_done < n_valid`` — the ``feasible=False`` verdict — and
their Q rows are left at zero exactly like ``BatchEvaluator._backward_q``.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

__all__ = [
    "DenseGraph",
    "dense_graph",
    "graph_from_pack",
    "sweep",
    "sweep_xla",
    "sweep_pallas",
    "level_loop_xla",
    "backward_q_xla",
    "bucket",
]


def bucket(n: int, quantum: int = 32) -> int:
    """Round ``n`` up to the next shape bucket (bounds recompiles)."""
    return max(quantum, quantum * ((int(n) + quantum - 1) // quantum))


@dataclasses.dataclass(frozen=True)
class DenseGraph:
    """Dense-padded adjacency of one instance's conjunctive DAG.

    ``pred_mat``/``succ_mat`` are ``(n_b, deg)`` index matrices padded with
    -1; ``adj[i, j]`` is True iff ``j -> i`` is a DAG edge (the mask form the
    Pallas kernel reduces over).  ``n`` is the real task count, ``n_b`` the
    shape bucket it is padded to.
    """

    n: int
    n_b: int
    pred_mat: np.ndarray   # (n_b, max_indeg)  int32, -1 padded
    succ_mat: np.ndarray   # (n_b, max_outdeg) int32, -1 padded
    adj: np.ndarray        # (n_b, n_b) bool; adj[i, j] == (j is DAG-pred of i)


def dense_from_csr(n: int, n_b: int, indptr: np.ndarray, idx: np.ndarray,
                   min_width: int = 1) -> np.ndarray:
    """CSR rows as a -1-padded ``(n_b, width)`` index matrix (row order
    preserved).  Shared by the sweep kernels and the device search engine."""
    deg = np.diff(indptr)
    width = max(min_width, int(deg.max()) if len(deg) else 1, 1)
    mat = np.full((n_b, width), -1, dtype=np.int32)
    if len(idx):
        owner = np.repeat(np.arange(n), deg)
        pos = np.arange(len(idx)) - np.repeat(indptr[:-1], deg)
        mat[owner, pos] = idx
    return mat


_dense_from_csr = dense_from_csr  # backward-compat alias


def _adj_mask(n: int, n_b: int, succ_indptr, succ_idx) -> np.ndarray:
    """``adj[i, j] == (j is DAG-pred of i)`` — the Pallas reduce mask."""
    adj = np.zeros((n_b, n_b), dtype=bool)
    src = np.repeat(np.arange(n), np.diff(succ_indptr))
    adj[succ_idx, src] = True
    return adj


def dense_graph(inst, n_bucket: int | None = None) -> DenseGraph:
    """Build the dense-padded adjacency for ``inst`` (a core.mdfg.Instance)."""
    n = inst.n_tasks
    n_b = n_bucket if n_bucket is not None else bucket(n)
    assert n_b >= n
    pred_mat = _dense_from_csr(n, n_b, inst.pred_indptr, inst.pred_idx)
    succ_mat = _dense_from_csr(n, n_b, inst.succ_indptr, inst.succ_idx)
    adj = _adj_mask(n, n_b, inst.succ_indptr, inst.succ_idx)
    return DenseGraph(n=n, n_b=n_b, pred_mat=pred_mat, succ_mat=succ_mat, adj=adj)


def graph_from_pack(inst, pack) -> DenseGraph:
    """A :class:`DenseGraph` that reuses an ``InstancePack``'s already-padded
    predecessor/successor matrices instead of re-walking the CSR (the
    ``repro.instances`` boundary: pack once, every sweep consumer reads the
    same arrays).  Only the Pallas mask ``adj`` is derived here."""
    adj = _adj_mask(inst.n_tasks, pack.n_b, inst.succ_indptr, inst.succ_idx)
    return DenseGraph(n=pack.n, n_b=pack.n_b, pred_mat=pack.pred_mat,
                      succ_mat=pack.succ_mat, adj=adj)


# --------------------------------------------------------------------------- #
# XLA (gather) implementation                                                  #
# --------------------------------------------------------------------------- #
def level_loop_xla(link_mat, link_vec, node_add, n_valid: int, active_rows):
    """The masked level-synchronous recursion, exposed for reuse.

    ``value[i] = node_add[i] + max(0, linked values)`` where the links are
    the dense ``link_mat (n_b, deg)`` slots plus the per-row ``link_vec``
    link; a task is ready iff all its links are done.  ``active_rows``
    masks whole rows out (used to skip infeasible rows in the backward
    sweep).  Returns ``(val, level, done)``.  Jit/vmap-friendly: every
    update is masked, so a vmapped-over-instances caller keeps exact
    per-instance semantics even when the lifted while_loop runs extra
    (no-op) levels for some rows.
    """
    import jax
    import jax.numpy as jnp

    fdt = node_add.dtype
    b, n_b = node_add.shape
    neg_inf = jnp.asarray(-jnp.inf, fdt)
    valid = (jnp.arange(n_b) < n_valid)[None, :]          # (1, n_b)
    link_pad = jnp.where(link_mat < 0, 0, link_mat)       # (n_b, deg)
    link_ok = link_mat >= 0
    lv_pad = jnp.where(link_vec < 0, 0, link_vec)         # (b, n_b)
    lv_ok = link_vec >= 0

    def cond(state):
        _, _, _, ready, lev = state
        return jnp.logical_and(ready.any(), lev <= n_valid)

    def body(state):
        val, level, done, ready, lev = state
        gathered = val[:, link_pad]                       # (b, n_b, deg)
        gmax = jnp.where(link_ok[None], gathered, neg_inf).max(axis=2)
        mval = jnp.where(lv_ok, jnp.take_along_axis(val, lv_pad, axis=1), neg_inf)
        base = jnp.maximum(jnp.maximum(gmax, mval), jnp.asarray(0.0, fdt))
        v = base + node_add
        val = jnp.where(ready, v, val)
        level = jnp.where(ready, lev, level)
        done = done | ready
        link_done = (~link_ok[None]) | done[:, link_pad]
        mdone = (~lv_ok) | jnp.take_along_axis(done, lv_pad, axis=1)
        ready = valid & active_rows & ~done & link_done.all(axis=2) & mdone
        return val, level, done, ready, lev + 1

    val = jnp.zeros((b, n_b), fdt)
    level = jnp.zeros((b, n_b), jnp.int32)
    done = jnp.zeros((b, n_b), bool)
    link_done = (~link_ok[None]) | done[:, link_pad]
    mdone = (~lv_ok) | jnp.take_along_axis(done, lv_pad, axis=1)
    ready = valid & active_rows & ~done & link_done.all(axis=2) & mdone
    state = (val, level, done, ready, jnp.int32(0))
    val, level, done, _, _ = jax.lax.while_loop(cond, body, state)
    return val, level, done


def backward_q_xla(succ_mat, dur, msucc, n_valid: int, active_rows=None):
    """Tails Q alone (Eq. 28) for already-scheduled rows: one backward level
    loop, bit-exact with ``BatchEvaluator._backward_q`` in float64."""
    import jax.numpy as jnp

    if active_rows is None:
        active_rows = jnp.ones((dur.shape[0], 1), bool)
    q, _, done = level_loop_xla(succ_mat, msucc, dur, n_valid, active_rows)
    return jnp.where(done, q, 0.0)


def sweep_xla(pred_mat, succ_mat, dur, mpred, msucc, n_valid: int,
              *, tails: bool = True):
    """Forward (+ optional backward) sweep in pure jnp.

    Shapes: ``pred_mat/succ_mat (n_b, deg)``, ``dur/mpred/msucc (B, n_b)``.
    Returns ``(start, finish, level, n_done, q)`` with ``q`` zeros when
    ``tails=False``.
    """
    import jax.numpy as jnp

    fdt = dur.dtype
    b, n_b = dur.shape
    neg_inf = jnp.asarray(-jnp.inf, fdt)
    valid = (jnp.arange(n_b) < n_valid)[None, :]          # (1, n_b)

    ones = jnp.ones((b, 1), bool)
    # forward: value = finish = max(preds' finish, 0) + dur
    finish, level, done = level_loop_xla(pred_mat, mpred, dur, n_valid, ones)
    # start is re-derived as the same masked max (NOT finish - dur, which
    # would not be bit-identical under rounding and breaks on inf durations)
    link_pad = jnp.where(pred_mat < 0, 0, pred_mat)
    link_ok = pred_mat >= 0
    gmax = jnp.where(link_ok[None], finish[:, link_pad], neg_inf).max(axis=2)
    mp_pad = jnp.where(mpred < 0, 0, mpred)
    mval = jnp.where(mpred >= 0, jnp.take_along_axis(finish, mp_pad, axis=1), neg_inf)
    start = jnp.where(done, jnp.maximum(jnp.maximum(gmax, mval),
                                        jnp.asarray(0.0, fdt)), 0.0)
    finish = jnp.where(done, finish, 0.0)
    n_done = (done & valid).sum(axis=1)
    if tails:
        feasible = (n_done == n_valid)[:, None]
        # mirror the scalar heads_tails operands (dur = finish - start):
        # (base + dur) - base can differ from dur in the last ulp, and the
        # bit-exactness contract is against the NumPy engine's Q
        q = backward_q_xla(succ_mat, finish - start, msucc, n_valid, feasible)
    else:
        q = jnp.zeros((b, n_b), fdt)
    return start, finish, level, n_done, q


# --------------------------------------------------------------------------- #
# Pallas kernel                                                                #
# --------------------------------------------------------------------------- #
_LANES = 128  # TPU vector lane width: the kernel's task axis is a multiple


@functools.lru_cache(maxsize=16)
def _build_pallas_sweep(n_b: int, n_valid: int, tails: bool, interpret: bool,
                        dtype_name: str):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    assert n_b % _LANES == 0
    fdt = jnp.dtype(dtype_name)
    neg_inf = float(-np.inf)

    def kernel(adj_ref, adjt_ref, mpred_ref, msucc_ref, dur_ref,
               start_ref, finish_ref, level_ref, ndone_ref, q_ref):
        # one candidate row per grid step; Mosaic cannot carry i1 vectors
        # through a loop, so every carried flag is an int32 0/1 array
        dur = dur_ref[:]                                   # (1, n_b)
        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, n_b), 1)
                 < n_valid).astype(jnp.int32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, _LANES), 2)

        def link_max(graph_ref, mlink, vals, fill):
            """``max`` of ``vals[j]`` over the j that link into each i: DAG
            edges ``graph[i, j]`` plus the machine link ``mlink[i]``.  The
            j axis is reduced one 128-lane chunk at a time, so the masked
            temporaries are (n_b, 128) whatever n_b is."""
            out = None
            for c in range(0, n_b, _LANES):
                g = graph_ref[:, pl.ds(c, _LANES)]
                linked = (g[None, :, :] != 0) \
                    | (mlink[:, :, None] == lane + c)
                v = jax.lax.slice_in_dim(vals, c, c + _LANES, axis=1)
                m = jnp.where(linked, v[:, None, :], fill).max(axis=2)
                out = m if out is None else jnp.maximum(out, m)
            return out

        def run(graph_ref, mlink, node_add, active):
            def ready_of(done):
                waiting = link_max(graph_ref, mlink, 1 - done, 0)
                return valid * active * (1 - done) * (1 - waiting)

            def cond(state):
                _, _, _, ready, lev = state
                return jnp.logical_and(ready.max() > 0, lev <= n_valid)

            def body(state):
                val, level, done, ready, lev = state
                base = jnp.maximum(link_max(graph_ref, mlink, val, neg_inf),
                                   0.0).astype(fdt)
                go = ready != 0
                val = jnp.where(go, base + node_add, val)
                level = jnp.where(go, lev, level)
                done = jnp.maximum(done, ready)
                return val, level, done, ready_of(done), lev + 1

            val = jnp.zeros((1, n_b), fdt)
            level = jnp.zeros((1, n_b), jnp.int32)
            done = jnp.zeros((1, n_b), jnp.int32)
            val, level, done, _, _ = jax.lax.while_loop(
                cond, body, (val, level, done, ready_of(done), jnp.int32(0)))
            return val, level, done != 0

        mpred = mpred_ref[:]
        finish, level, done = run(adj_ref, mpred, dur, 1)
        head = link_max(adj_ref, mpred, finish, neg_inf)
        start = jnp.where(done, jnp.maximum(head, 0.0).astype(fdt), 0.0)
        finish = jnp.where(done, finish, 0.0)
        n_done = jnp.where(done, valid, 0).sum(axis=1, keepdims=True)
        start_ref[:] = start
        finish_ref[:] = finish
        level_ref[:] = level
        ndone_ref[:] = n_done
        if tails:
            # successors: the transposed DAG plus the machine successor;
            # operands mirror the scalar heads_tails (dur = finish - start)
            feasible = (n_done == n_valid).astype(jnp.int32)
            q, _, qdone = run(adjt_ref, msucc_ref[:], finish - start,
                              feasible)
            q_ref[:] = jnp.where(qdone, q, 0.0)
        else:
            q_ref[:] = jnp.zeros_like(dur)

    @jax.jit
    def call(adj, adjt, mpred, msucc, dur):
        # rows ride as (b, 1, n_b): a (1, n_b) block spans the array's last
        # two dims, which is a tile Mosaic accepts for any row count
        b = dur.shape[0]
        row_spec = pl.BlockSpec((None, 1, n_b), lambda i: (i, 0, 0))
        graph_spec = pl.BlockSpec((n_b, n_b), lambda i: (0, 0))
        start, finish, level, n_done, q = pl.pallas_call(
            kernel,
            grid=(b,),
            in_specs=[graph_spec, graph_spec, row_spec, row_spec, row_spec],
            out_specs=[row_spec, row_spec, row_spec,
                       pl.BlockSpec((None, 1, 1), lambda i: (i, 0, 0)),
                       row_spec],
            out_shape=[
                jax.ShapeDtypeStruct((b, 1, n_b), fdt),
                jax.ShapeDtypeStruct((b, 1, n_b), fdt),
                jax.ShapeDtypeStruct((b, 1, n_b), jnp.int32),
                jax.ShapeDtypeStruct((b, 1, 1), jnp.int32),
                jax.ShapeDtypeStruct((b, 1, n_b), fdt),
            ],
            interpret=interpret,
        )(adj, adjt, mpred[:, None, :], msucc[:, None, :], dur[:, None, :])
        return (start[:, 0], finish[:, 0], level[:, 0], n_done[:, 0, 0],
                q[:, 0])

    return call


def sweep_pallas(adj, dur, mpred, msucc, n_valid: int, *, tails: bool = True,
                 interpret: bool = False):
    """Pallas sweep over ``(B, n_b)`` rows, one row per grid step.

    Tasks are padded to a multiple of 128 lanes (pad tasks are never valid,
    so they stay at zero).  Returns ``(start, finish, level, n_done, q)``.
    """
    import jax.numpy as jnp

    b, n_b = dur.shape
    n_p = _LANES * ((n_b + _LANES - 1) // _LANES)

    def pad(a, fill, dtype):
        return jnp.pad(jnp.asarray(a, dtype), ((0, 0), (0, n_p - n_b)),
                       constant_values=fill)

    graph = np.zeros((n_p, n_p), np.int32)
    graph[:n_b, :n_b] = adj
    call = _build_pallas_sweep(n_p, int(n_valid), bool(tails),
                               bool(interpret), jnp.dtype(dur.dtype).name)
    start, finish, level, n_done, q = call(
        jnp.asarray(graph), jnp.asarray(graph.T.copy()),
        pad(mpred, -1, jnp.int32), pad(msucc, -1, jnp.int32),
        pad(dur, 0, dur.dtype))
    return (start[:, :n_b], finish[:, :n_b], level[:, :n_b], n_done,
            q[:, :n_b])


# --------------------------------------------------------------------------- #
# dispatcher                                                                   #
# --------------------------------------------------------------------------- #
def default_impl() -> str:
    """``pallas`` on TPU, the XLA gather lowering elsewhere (CPU/GPU)."""
    import jax

    return "pallas" if jax.default_backend() == "tpu" else "xla"


def sweep(graph: DenseGraph, dur, mpred, msucc, *, tails: bool = True,
          impl: str | None = None):
    """Run the sweep with the requested implementation.

    ``impl`` ∈ {"xla", "pallas", "pallas_interpret", None=auto}.  ``dur``,
    ``mpred``, ``msucc`` are ``(B, n_b)`` device/NumPy arrays.
    """
    import jax.numpy as jnp

    impl = impl or default_impl()
    if impl == "xla":
        return sweep_xla(jnp.asarray(graph.pred_mat), jnp.asarray(graph.succ_mat),
                         dur, mpred, msucc, graph.n, tails=tails)
    if impl in ("pallas", "pallas_interpret"):
        return sweep_pallas(graph.adj, dur, mpred, msucc, graph.n, tails=tails,
                            interpret=impl == "pallas_interpret")
    raise ValueError(f"unknown schedule-DP impl {impl!r}")
