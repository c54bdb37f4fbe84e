"""Makespan lower bound of an instance: the denominator of ``mk_over_lb``.

A copy of the program's ``repro.instances.bounds.lower_bound`` written
against :class:`bench.instances.Case`, so that the quality yardstick does
not move with the program.  The bound is the largest of three that hold
for every schedule:

* critical path: the longest precedence chain with every task at its
  best-case duration (fastest compatible core, every block on its fastest
  allowed tier);
* work: the sum of best-case durations spread over all cores;
* memory spill: the work bound plus, per task, the touched fast-eligible
  volume above the combined finite-tier capacity priced at the cheapest
  slow-over-fast access gap.
"""
from __future__ import annotations

import numpy as np

__all__ = ["lower_bound"]


def _segment_sums(values: np.ndarray, indptr: np.ndarray) -> np.ndarray:
    c = np.zeros(len(values) + 1)
    np.cumsum(values, out=c[1:])
    return c[indptr[1:]] - c[indptr[:-1]]


def best_case_durations(case) -> np.ndarray:
    at = np.where(case.data_mem_ok[None, :, :].transpose(0, 2, 1),
                  case.access_time[:, :, None], np.inf)      # (P, M, D)
    at_min = at.min(axis=1)                                  # (P, D)
    t_in = np.stack([_segment_sums(case.data_size[case.in_idx] * at_min[p, case.in_idx],
                                   case.in_indptr) for p in range(case.n_procs)])
    t_out = np.stack([_segment_sums(case.data_size[case.out_idx] * at_min[p, case.out_idx],
                                    case.out_indptr) for p in range(case.n_procs)])
    return (t_in.T + case.proc_time + t_out.T).min(axis=1)


def _critical_path(case, dur: np.ndarray) -> float:
    edges = case.precedence()
    succs = [[] for _ in range(case.n_tasks)]
    indeg = np.zeros(case.n_tasks, dtype=np.int64)
    for u, v in edges:
        succs[u].append(v)
        indeg[v] += 1
    head = np.zeros(case.n_tasks)
    ready = list(np.nonzero(indeg == 0)[0])
    seen = 0
    while ready:
        u = ready.pop()
        seen += 1
        fin = head[u] + dur[u]
        for v in succs[u]:
            head[v] = max(head[v], fin)
            indeg[v] -= 1
            if indeg[v] == 0:
                ready.append(v)
    if seen != case.n_tasks:
        raise ValueError(f"{case.name}: precedence graph is cyclic")
    return float((head + dur).max()) if case.n_tasks else 0.0


def _spill_bound(case, dur: np.ndarray) -> float:
    work = float(dur.sum())
    finite = np.isfinite(case.mem_cap)
    if finite.all() or not finite.any():
        return work / max(1, case.n_procs)
    fast_cap = float(case.mem_cap[finite].sum())
    fast_ok = case.data_mem_ok[:, finite].any(axis=1)
    size = np.where(fast_ok, case.data_size, 0.0)
    touched = (_segment_sums(size[case.in_idx], case.in_indptr)
               + _segment_sums(size[case.out_idx], case.out_indptr))
    spill = float(np.maximum(0.0, touched - fast_cap).sum())
    gap = float((case.access_time[:, ~finite].min(axis=1)
                 - case.access_time.min(axis=1)).min())
    return (work + spill * max(0.0, gap)) / max(1, case.n_procs)


def lower_bound(case) -> float:
    dur = best_case_durations(case)
    return max(_critical_path(case, dur), float(dur.sum()) / max(1, case.n_procs),
               _spill_bound(case, dur))
