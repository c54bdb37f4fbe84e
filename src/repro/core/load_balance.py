"""Load-balancing baseline (§V-C) — the comparator the paper beats by 5–25 %.

"It always selects the task that can start earliest, sorts them on the
machine according to the ascending order of the earliest time that can start
to move, and always selects the most idle core."  Memory is allocated with
the same greedy fast-first rule as the constructor.
"""
from __future__ import annotations

import numpy as np

from .greedy import GreedyState, _candidate_ends, _commit_task, _place_initial_input
from .mdfg import Instance
from .solution import Solution

__all__ = ["load_balance"]


def load_balance(inst: Instance, rng: np.random.Generator | int = 0) -> Solution:
    rng = np.random.default_rng(rng)
    n = inst.n_tasks
    assign = np.full(n, -1, dtype=np.int64)
    mem = np.full(inst.n_data, -1, dtype=np.int64)
    proc_seq: list[list[int]] = [[] for _ in range(inst.n_procs)]
    state = GreedyState.empty(inst)
    for d in np.nonzero(inst.producer < 0)[0]:
        m = _place_initial_input(inst, state, int(d))
        if m is not None:
            mem[d] = m

    n_preds = np.diff(inst.pred_indptr)
    n_sched = np.zeros(n, dtype=np.int64)
    frontier = {int(i) for i in np.nonzero(n_preds == 0)[0]}
    remaining = set(range(n))
    slack = np.zeros(n)  # LB ignores slack; reuse greedy mem allocator signature

    while remaining:
        # earliest-startable task first
        def est(i: int) -> float:
            p = inst.preds(i)
            return float(state.finish[p].max()) if len(p) else 0.0

        t = min(sorted(frontier), key=est)
        ready = est(t)
        # most idle compatible core (earliest free; ties → least busy)
        procs = inst.compatible_procs(t)
        c = int(min(procs, key=lambda p: (state.core_free[p], len(proc_seq[p]))))
        ends, starts, outs, choice = _candidate_ends(
            inst, state, mem, t, np.array([c]), ready, slack)

        assign[t] = c
        proc_seq[c].append(t)
        _commit_task(inst, state, mem, t, c, starts[0], ends[0], outs, choice[0])
        remaining.discard(t)
        frontier.discard(t)
        for v in inst.succs(t):
            n_sched[v] += 1
            if n_sched[v] == n_preds[v] and v in remaining:
                frontier.add(int(v))

    for d in np.nonzero(mem < 0)[0]:
        cm = inst.compatible_mems(d)
        mem[d] = int(cm[np.argmax(inst.mem_level[cm])])
    return Solution(assign=assign, mem=mem, proc_seq=proc_seq)
