"""Greedy initial-solution construction — Algorithm 1 of the paper.

Iteratively selects the most important frontier task (four selectable
priority strategies, §V-B), tries every compatible core, greedily allocates
memory for the data blocks the task produces (fast tiers first, capacity
checked over block lifetimes), and commits the (core, memory) choice with the
earliest task end time.
"""
from __future__ import annotations

import collections
import dataclasses
import threading

import numpy as np

from .mdfg import InfeasibleInstanceError, Instance
from .solution import Solution

__all__ = ["construct_greedy", "GreedyState", "PEAK_QUERIES", "STRATEGIES", "TierIntervals"]

STRATEGIES = ("slack_first", "r_first", "random", "relax_r")


PEAK_QUERIES: "collections.Counter[str]" = collections.Counter()
"""Tier-capacity peak queries, one per distinct start asked (``queries``),
and by the path that answered them: ``incremental`` (a lookup in the tier's
suffix maxima, integer-valued block sizes) or ``per_query`` (a sort per
start)."""
_PEAK_QUERIES_LOCK = threading.Lock()   # constructions may run on several threads


def _exact_sizes(size: np.ndarray) -> bool:
    """Whether every sum of block sizes is exact in float64, so that it does
    not depend on the order of the terms: non-negative integers whose total
    stays below 2**52."""
    return bool((size >= 0).all() and (size == np.floor(size)).all()
                and size.sum() < 2.0**52)


class TierIntervals:
    """The committed block intervals of one finite tier, and the capacity
    question Algorithm 1 asks before placing a block there.

    Births, deaths (inf until every consumer of the block is scheduled) and
    sizes live in growable arrays, updated in place by commits and death
    updates. The peak a query answers is the one a sweep gives over the
    events ``(max(birth, start), +size)`` and ``(death, -size)`` of every
    interval alive after ``start``, sorted by (time, delta), frees before adds
    at one time, and summed in that order.

    Where every block size is a non-negative integer (``exact``) all those
    sums are exact, so the order does not matter: the tier keeps its usage
    after each distinct event time and the suffix maxima of it, and a start
    is a ``searchsorted``. That relies on no block dying before its birth,
    which holds since a block's consumers finish after its producer starts.
    Otherwise each start sorts its own events and sums them in the sweep's
    order, which gives the sweep's answer to the bit.
    """

    def __init__(self, exact: bool) -> None:
        self.exact = exact
        self.n = 0
        self.birth = np.empty(64)
        self.death = np.empty(64)
        self.size = np.empty(64)
        self._times: np.ndarray | None = None   # distinct event times, sorted
        self._suffix_max: np.ndarray | None = None

    def add(self, birth: float, size: float) -> int:
        """Commit a block alive from ``birth`` until further notice."""
        k = self.n
        if k == len(self.birth):
            grow = np.empty(k)
            self.birth, self.death, self.size = (
                np.concatenate([a, grow]) for a in (self.birth, self.death, self.size))
        self.birth[k], self.death[k], self.size[k] = birth, np.inf, size
        self.n = k + 1
        self._times = None
        return k

    def set_death(self, k: int, death: float) -> None:
        self.death[k] = death
        self._times = None

    def peaks(self, starts: np.ndarray, size: float,
              tentative: list[list[float]]) -> np.ndarray:
        """Peak usage over ``[start, inf)`` for each of ``starts``, if a block
        of ``size`` is added at that start beside the sibling sizes
        ``tentative[i]`` placed there tentatively."""
        with _PEAK_QUERIES_LOCK:
            PEAK_QUERIES["queries"] += len(starts)
            PEAK_QUERIES["incremental" if self.exact else "per_query"] += len(starts)
        if not self.exact:
            return np.array([self._peak_sorted(st, size, tent)
                             for st, tent in zip(starts, tentative)])
        if self._times is None:
            self._index()
        tent = np.array([sum(t) for t in tentative], dtype=np.float64)
        at = np.searchsorted(self._times, starts, side="right")
        return size + tent + self._suffix_max[at]

    def _index(self) -> None:
        n = self.n
        birth, death, size = self.birth[:n], self.death[:n], self.size[:n]
        freed = np.isfinite(death)
        times = np.concatenate([birth, death[freed]])
        order = np.argsort(times, kind="stable")
        times = times[order]
        run = np.cumsum(np.concatenate([size, -size[freed]])[order])
        last = np.ones(len(times), dtype=bool)     # last event of each time
        last[:-1] = times[1:] != times[:-1]
        self._times = times[last]
        # usage after each distinct time, led by the 0 before the first
        usage = np.concatenate([[0.0], run[last]])
        self._suffix_max = np.maximum.accumulate(usage[::-1])[::-1]

    def _peak_sorted(self, start: float, size: float, tentative: list[float]) -> float:
        n = self.n
        live = self.death[:n] > start
        birth = np.maximum(self.birth[:n][live], start)
        death, sz = self.death[:n][live], self.size[:n][live]
        freed = np.isfinite(death)
        times = np.concatenate([[start], birth, death[freed], np.full(len(tentative), start)])
        delta = np.concatenate([[size], sz, -sz[freed], tentative])
        run = np.cumsum(delta[np.lexsort((delta, times))])
        return max(0.0, float(run.max()))


@dataclasses.dataclass
class GreedyState:
    """Mutable bookkeeping during construction."""

    finish: np.ndarray            # committed task finish times (nan = unscheduled)
    start: np.ndarray
    core_free: np.ndarray
    tier_order: list[int]         # memories in ``mem_level`` order
    # per finite memory: its committed intervals; a block dies when every
    # consumer is scheduled (until then conservatively never)
    tiers: dict[int, TierIntervals]
    interval_of_block: dict[int, tuple[int, int]]  # d -> (mem, index in tiers[mem])

    @classmethod
    def empty(cls, inst: Instance) -> "GreedyState":
        exact = _exact_sizes(inst.data_size)
        return cls(
            finish=np.full(inst.n_tasks, np.nan),
            start=np.full(inst.n_tasks, np.nan),
            core_free=np.zeros(inst.n_procs),
            tier_order=[int(m) for m in np.argsort(inst.mem_level)],
            tiers={int(m): TierIntervals(exact)
                   for m in np.flatnonzero(np.isfinite(inst.mem_cap))},
            interval_of_block={},
        )

    def commit(self, d: int, m: int, birth: float, size: float) -> None:
        """Block ``d`` lives in memory ``m`` from ``birth`` on."""
        tier = self.tiers.get(m)
        if tier is not None:
            self.interval_of_block[d] = (m, tier.add(birth, size))


def _estimate_rq(
    inst: Instance,
    topo: np.ndarray,
    t_est: np.ndarray,
    finish: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """R/Q/Slack over the DAG.

    Preprocessing (§IV-A.1): uses execution-time estimates ``t_est`` only;
    as tasks commit, their actual ``finish`` replaces the estimate so that
    priorities stay fresh (the paper's ``freshRQSlack``).
    """
    n = inst.n_tasks
    r = np.zeros(n)
    scheduled = ~np.isnan(finish)
    for u in topo:
        if scheduled[u]:
            continue
        best = 0.0
        for j in inst.preds(u):
            f = finish[j] if scheduled[j] else r[j] + t_est[j]
            if f > best:
                best = f
        r[u] = best
    q = np.zeros(n)
    for u in topo[::-1]:
        best = 0.0
        for j in inst.succs(u):
            if q[j] > best:
                best = q[j]
        q[u] = t_est[u] + best
    cmax = float((r + q).max()) if n else 0.0
    slack = cmax - r - q
    return r, q, slack


def _alloc_outputs(
    inst: Instance,
    state: GreedyState,
    task: int,
    starts: np.ndarray,
    slack: np.ndarray,
) -> tuple[list[int], np.ndarray]:
    """Greedy fast-first memory choice for the blocks ``task`` produces, for
    every candidate start at once.

    Blocks are sorted by the minimum Slack of their consumers (most urgent
    first — paper §IV-A.2); tiers tried in ``mem_level`` order. Returns the
    blocks in that order and ``choice[i, j]``, the memory of block ``j`` when
    the task starts at ``starts[i]``. The choice depends on the start alone,
    so each distinct start is asked once.
    """
    outs = [int(d) for d in inst.outputs(task)]
    outs.sort(key=lambda d: min([slack[c] for c in inst.consumers(d)], default=np.inf))
    uniq, inv = np.unique(starts, return_inverse=True)
    choice = np.full((len(uniq), len(outs)), -1, dtype=np.int64)
    # tentative placements of this task's earlier outputs count against
    # capacity, else sibling blocks jointly overflow
    tentative = {m: [[] for _ in uniq] for m in state.tiers}
    for j, d in enumerate(outs):
        size = float(inst.data_size[d])
        left = np.arange(len(uniq))
        for m in state.tier_order:
            if not len(left):
                break
            if not inst.data_mem_ok[d, m]:
                continue
            tier = state.tiers.get(m)
            if tier is None:
                choice[left, j] = m
                break
            tent = tentative[m]
            fits = tier.peaks(uniq[left], size, [tent[i] for i in left]) <= inst.mem_cap[m]
            for i in left[fits]:
                tent[i].append(size)
            choice[left[fits], j] = m
            left = left[~fits]
    choice = choice[inv]
    unplaced = (choice < 0).any(axis=1)
    if unplaced.any():
        i = int(np.argmax(unplaced))
        d = outs[int(np.argmax(choice[i] < 0))]
        tried = [m for m in state.tier_order if inst.data_mem_ok[d, m]]
        raise InfeasibleInstanceError(
            f"no memory tier can hold block {d} (size {inst.data_size[d]:g}) "
            f"produced by task {task} at t={starts[i]:g}; compatible tiers tried: "
            f"{tried or 'none'}",
            block=d, task=task, tiers_tried=tuple(tried),
        )
    return outs, choice


def _row_sums(x: np.ndarray) -> np.ndarray:
    """Each row summed left to right, as Python's ``sum`` adds."""
    return np.cumsum(x, axis=1)[:, -1] if x.shape[1] else np.zeros(len(x))


def _candidate_ends(
    inst: Instance,
    state: GreedyState,
    mem: np.ndarray,
    task: int,
    procs: np.ndarray,
    ready: float,
    slack: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, list[int], np.ndarray]:
    """End time of ``task`` on each core of ``procs``, with its start there,
    and the output blocks with the memory chosen for each on each core."""
    starts = np.maximum(ready, state.core_free[procs])
    outs, choice = _alloc_outputs(inst, state, task, starts, slack)
    ins = inst.inputs(task)
    in_mem = np.where(mem[ins] >= 0, mem[ins], inst.n_mems - 1)
    at = inst.access_time[procs]
    t_in = _row_sums(inst.data_size[ins] * at[:, in_mem])
    t_out = _row_sums(inst.data_size[outs] * np.take_along_axis(at, choice, axis=1))
    return starts + t_in + inst.proc_time[task, procs] + t_out, starts, outs, choice


def _place_initial_input(inst: Instance, state: GreedyState, d: int) -> int | None:
    """Commit initial-input block ``d``, alive from t=0, to the first memory
    in preference order that holds it; None where none does."""
    size = float(inst.data_size[d])
    for m in state.tier_order:
        if not inst.data_mem_ok[d, m]:
            continue
        tier = state.tiers.get(m)
        if tier is None or tier.peaks(np.zeros(1), size, [[]])[0] <= inst.mem_cap[m]:
            state.commit(d, m, 0.0, size)
            return m
    return None


def _close_consumed_blocks(inst: Instance, state: GreedyState, task: int) -> None:
    """Refine death times: a block is released once all consumers finished."""
    for d in inst.inputs(task):
        loc = state.interval_of_block.get(int(d))
        if loc is None:
            continue
        fin = state.finish[inst.consumers(d)]
        if np.isnan(fin).any():
            continue
        m, k = loc
        state.tiers[m].set_death(k, float(fin.max()))


def _commit_task(
    inst: Instance,
    state: GreedyState,
    mem: np.ndarray,
    task: int,
    core: int,
    start: float,
    end: float,
    outs: list[int],
    out_mems: np.ndarray,
) -> None:
    """Run ``task`` on ``core`` over [start, end], its outputs in ``out_mems``."""
    state.start[task] = start
    state.finish[task] = end
    state.core_free[core] = end
    for d, m in zip(outs, out_mems):
        mem[d] = m
        state.commit(d, int(m), start, float(inst.data_size[d]))
    _close_consumed_blocks(inst, state, task)


def construct_greedy(
    inst: Instance,
    strategy: str = "slack_first",
    rng: np.random.Generator | int = 0,
    relax_eps: float = 0.02,
) -> Solution:
    """Algorithm 1.  ``strategy`` ∈ {slack_first, r_first, random, relax_r}."""
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r}")
    rng = np.random.default_rng(rng)
    n = inst.n_tasks
    topo = inst.topological_order()
    t_est = np.where(
        np.isfinite(inst.proc_time), inst.proc_time, np.inf
    ).min(axis=1)

    assign = np.full(n, -1, dtype=np.int64)
    mem = np.full(inst.n_data, -1, dtype=np.int64)
    proc_seq: list[list[int]] = [[] for _ in range(inst.n_procs)]
    state = GreedyState.empty(inst)
    # initial input data (producer = -1): allocate up front, alive from t=0
    for d in np.nonzero(inst.producer < 0)[0]:
        m = _place_initial_input(inst, state, int(d))
        if m is None:
            tried = [k for k in state.tier_order if inst.data_mem_ok[d, k]]
            raise InfeasibleInstanceError(
                f"no memory tier can hold initial-input block {d} "
                f"(size {inst.data_size[d]:g}, alive from t=0); compatible tiers "
                f"tried: {tried or 'none'}",
                block=int(d), task=-1, tiers_tried=tuple(tried),
            )
        mem[d] = m

    n_sched_preds = np.zeros(n, dtype=np.int64)
    n_preds = np.diff(inst.pred_indptr)
    remaining = set(range(n))
    frontier = {int(i) for i in np.nonzero(n_preds == 0)[0]}

    r, q, slack = _estimate_rq(inst, topo, t_est, state.finish)
    rounds_since_refresh = 0

    while remaining:
        # ---- select task (§V-B strategies) --------------------------------
        cand = sorted(frontier)
        if strategy == "random":
            t = int(rng.choice(cand))
        else:
            def min_succ_slack(i: int) -> float:
                ss = inst.succs(i)
                return float(slack[ss].min()) if len(ss) else np.inf

            if strategy == "r_first":
                t = min(cand, key=lambda i: (r[i], slack[i], min_succ_slack(i)))
            elif strategy == "slack_first":
                t = min(cand, key=lambda i: (slack[i], r[i], min_succ_slack(i)))
            else:  # relax_r
                rmin = min(r[i] for i in cand)
                width = relax_eps * max(1.0, float(r.max()))
                close = [i for i in cand if r[i] <= rmin + width]
                t = min(close, key=lambda i: (slack[i], r[i]))

        # ---- evaluate every compatible core --------------------------------
        preds = inst.preds(t)
        ready = float(state.finish[preds].max()) if len(preds) else 0.0
        procs = inst.compatible_procs(t)
        ends, starts, outs, choice = _candidate_ends(inst, state, mem, t, procs, ready, slack)
        k = int(np.argmin(ends))  # first of the earliest ends, in core order

        # ---- commit ---------------------------------------------------------
        c = int(procs[k])
        assign[t] = c
        proc_seq[c].append(t)
        _commit_task(inst, state, mem, t, c, starts[k], ends[k], outs, choice[k])

        remaining.discard(t)
        frontier.discard(t)
        for v in inst.succs(t):
            n_sched_preds[v] += 1
            if n_sched_preds[v] == n_preds[v] and v in remaining:
                frontier.add(int(v))

        rounds_since_refresh += 1
        if rounds_since_refresh >= 16 or not frontier:
            r, q, slack = _estimate_rq(inst, topo, t_est, state.finish)
            rounds_since_refresh = 0

    # unassigned blocks (no producer path) → slowest compatible tier
    for d in np.nonzero(mem < 0)[0]:
        mem[d] = int(inst.compatible_mems(d)[np.argmax(inst.mem_level[inst.compatible_mems(d)])])
    return Solution(assign=assign, mem=mem, proc_seq=proc_seq)
