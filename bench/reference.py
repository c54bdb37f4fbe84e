"""Plain reference for a served schedule: the paper's §III constraints.

A condensed copy of the program's independent certificate checker
(``repro.analysis.certify``), written against :class:`bench.instances.Case`
so that no change to the program can loosen it.  It shares nothing with
the program's evaluators:

* durations are summed per task with plain loops over the input and
  output blocks, ``t_in + PT + t_out`` priced by ``AT(core, tier)``
  (eqs. 4-5);
* start and finish times come from a machine-head simulation: each core
  dispatches the head of its sequence once the task's DAG predecessors
  have finished; a pass with no progress is a disjunctive cycle;
* precedence is re-derived from ``task_edges`` and producer->consumer
  pairs;
* capacity is an event sweep per finite tier over block lifetimes,
  releases before acquires at equal instants (§IV-C).

``dtype`` sets the precision of every duration and time.  The
configuration states float64; the control computes the same schedule in
float32, the nearest precision below, and must fail the comparison.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Answer", "durations", "simulate", "makespan", "violations"]

TOL = 1e-6


@dataclasses.dataclass(frozen=True)
class Answer:
    """A schedule handed back by the system: core per task, tier per
    block, task order per core, and the makespan and memory feasibility
    the system claims for it."""

    assign: np.ndarray
    mem: np.ndarray
    proc_seq: tuple
    makespan: float
    feasible: bool


def durations(case, assign, mem, dtype=np.float64) -> np.ndarray:
    """``dur(i) = t_in + PT + t_out`` for every task, in ``dtype``."""
    at = case.access_time.astype(dtype)
    size = case.data_size.astype(dtype)
    pt = case.proc_time.astype(dtype)
    dur = np.empty(case.n_tasks, dtype=dtype)
    for i in range(case.n_tasks):
        p = int(assign[i])
        t = pt[i, p]
        for d in case.in_idx[case.in_indptr[i]:case.in_indptr[i + 1]]:
            t = dtype(t + size[d] * at[p, int(mem[d])])
        for d in case.out_idx[case.out_indptr[i]:case.out_indptr[i + 1]]:
            t = dtype(t + size[d] * at[p, int(mem[d])])
        dur[i] = t
    return dur


def _preds(case) -> list:
    preds = [[] for _ in range(case.n_tasks)]
    for u, v in case.precedence():
        preds[int(v)].append(int(u))
    return preds


def simulate(case, proc_seq, dur, preds=None):
    """Machine-head simulation.  Returns ``(start, finish, stuck)``; a
    non-empty ``stuck`` lists the head tasks of a disjunctive cycle."""
    preds = _preds(case) if preds is None else preds
    dtype = dur.dtype.type
    seqs = [list(map(int, s)) for s in proc_seq]
    heads = [0] * len(seqs)
    free = [dtype(0)] * len(seqs)
    done = np.zeros(case.n_tasks, dtype=bool)
    start = np.full(case.n_tasks, np.nan, dtype=dur.dtype)
    finish = np.full(case.n_tasks, np.nan, dtype=dur.dtype)
    left = sum(len(s) for s in seqs)
    while left:
        progress = False
        for p, seq in enumerate(seqs):
            while heads[p] < len(seq):
                t = seq[heads[p]]
                if not all(done[u] for u in preds[t]):
                    break
                s = free[p]
                for u in preds[t]:
                    s = max(s, finish[u])
                start[t] = s
                finish[t] = dtype(s + dur[t])
                free[p] = finish[t]
                done[t] = True
                heads[p] += 1
                left -= 1
                progress = True
        if not progress:
            return start, finish, [seq[heads[p]] for p, seq in enumerate(seqs)
                                   if heads[p] < len(seq)]
    return start, finish, []


def makespan(case, ans: Answer, dtype=np.float64) -> float:
    """The latest finish of ``ans``'s schedule, computed in ``dtype``
    (NaN when its core orders deadlock)."""
    dur = durations(case, ans.assign, ans.mem, dtype)
    _, finish, stuck = simulate(case, ans.proc_seq, dur)
    return float("nan") if stuck else float(np.max(finish))


def _structure(case, ans: Answer) -> list:
    out = []
    n = case.n_tasks
    assign, mem = np.asarray(ans.assign), np.asarray(ans.mem)
    if len(assign) != n or len(mem) != case.n_data:
        return [f"assignment: {len(assign)} cores for {n} tasks, "
                f"{len(mem)} tiers for {case.n_data} blocks"]
    for i in range(n):
        p = int(assign[i])
        if not 0 <= p < case.n_procs or not np.isfinite(case.proc_time[i, p]):
            out.append(f"assignment: task {i} on invalid or incompatible core {p}")
    seen = np.zeros(n, dtype=np.int64)
    for p, seq in enumerate(ans.proc_seq):
        for t in seq:
            t = int(t)
            if not 0 <= t < n:
                out.append(f"assignment: core {p} sequences unknown task {t}")
                continue
            seen[t] += 1
            if int(assign[t]) != p:
                out.append(f"assignment: task {t} sequenced on core {p} but "
                           f"assigned to {int(assign[t])}")
    out += [f"assignment: task {int(t)} sequenced {int(seen[t])} times"
            for t in np.nonzero(seen != 1)[0]]
    for d in range(case.n_data):
        m = int(mem[d])
        if not 0 <= m < case.n_mems or not case.data_mem_ok[d, m]:
            out.append(f"allocation: block {d} in invalid or incompatible tier {m}")
    return out


def _capacity(case, mem, start, finish) -> list:
    out = []
    for m in range(case.n_mems):
        cap = float(case.mem_cap[m])
        if not np.isfinite(cap):
            continue
        events = []
        for d in np.nonzero(np.asarray(mem) == m)[0]:
            prod = int(case.producer[d])
            birth = 0.0 if prod < 0 else float(start[prod])
            death = birth if prod < 0 else float(finish[prod])
            for c in case.cons_idx[case.cons_indptr[d]:case.cons_indptr[d + 1]]:
                death = max(death, float(finish[c]))
            size = float(case.data_size[d])
            events += [(birth, size), (death, -size)]
        events.sort()  # releases (negative) first at equal instants
        usage, peak = 0.0, 0.0
        for _, delta in events:
            usage += delta
            peak = max(peak, usage)
        if peak > cap * (1.0 + TOL) + TOL:
            out.append(f"capacity: tier {m} peaks at {peak:.6g} > {cap:.6g}")
    return out


def violations(case, ans: Answer) -> list:
    """Every §III constraint ``ans`` breaks, as ``"kind: detail"`` lines.

    Beyond the constraints: the claimed makespan must be the simulated
    one within ``TOL`` (relative), and a claim of memory feasibility must
    agree with the capacity sweep."""
    out = _structure(case, ans)
    if out:
        return out
    preds = _preds(case)
    dur = durations(case, ans.assign, ans.mem)
    start, finish, stuck = simulate(case, ans.proc_seq, dur, preds)
    if stuck:
        return [f"precedence: core orders deadlock at head tasks {stuck}"]
    mk = float(np.max(finish))
    tol = TOL * max(1.0, abs(mk))
    for v, ps in enumerate(preds):
        for u in ps:
            if finish[u] > start[v] + tol:
                out.append(f"precedence: task {v} starts before task {u} ends")
    for d in range(case.n_data):
        prod = int(case.producer[d])
        birth = 0.0 if prod < 0 else float(start[prod])
        for c in case.cons_idx[case.cons_indptr[d]:case.cons_indptr[d + 1]]:
            if start[c] + tol < birth:
                out.append(f"residency: task {int(c)} reads block {d} before it exists")
    cap = _capacity(case, ans.mem, start, finish)
    if ans.feasible:
        out += cap
    elif not cap:
        out.append("feasibility: claimed infeasible, but every tier fits")
    if not abs(ans.makespan - mk) <= tol:  # NaN-safe
        out.append(f"makespan: claimed {ans.makespan!r}, simulated {mk!r}")
    return out
