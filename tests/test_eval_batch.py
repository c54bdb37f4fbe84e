"""Batched evaluation engine: bit-exact parity with the scalar oracle,
cyclic-candidate verdicts, backend plumbing, and the tabu rewiring."""
import dataclasses

import numpy as np
import pytest

from repro.core import TSParams, random_instance, solve
from repro.core.eval_batch import BatchEvaluator, batch_evaluate, pack_solutions
from repro.core.solution import (
    Solution,
    exact_schedule,
    heads_tails,
    memory_feasible,
    memory_peaks,
)
from repro.core.tabu import _cc_moves, _n7_moves, apply_move


def neighbor_candidates(seed, n_tasks=50, n_data=120, k=48):
    """The tabu hot-path workload: a greedy incumbent plus its first k
    neighborhood moves (a mix of acyclic and cyclic candidates)."""
    inst = random_instance(seed, n_tasks=n_tasks, n_data=n_data)
    sol = solve(inst, "greedy:slack_first", seed=seed).solution
    sched = exact_schedule(inst, sol)
    r, q, _, crit = heads_tails(inst, sol, sched)
    moves = _n7_moves(sol, crit) + _cc_moves(inst, sol, crit, r, sched.start, 5)
    cands = [sol.copy()]
    for m in moves[: k - 1]:
        c = sol.copy()
        apply_move(c, m)
        cands.append(c)
    return inst, cands


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_bit_exact_parity_with_scalar(seed):
    inst, cands = neighbor_candidates(seed)
    ev = batch_evaluate(inst, cands, tails=True, peaks=True)
    n_cyclic = 0
    for i, c in enumerate(cands):
        s = exact_schedule(inst, c)
        if s is None:
            # cyclic disjunctive graph: same verdict, row masked out
            assert not ev.feasible[i]
            assert np.isinf(ev.makespan[i])
            assert ev.schedule(i) is None
            n_cyclic += 1
            continue
        assert ev.feasible[i]
        assert np.array_equal(s.start, ev.start[i])
        assert np.array_equal(s.finish, ev.finish[i])
        assert s.makespan == float(ev.makespan[i])
        _, q, slack, crit = heads_tails(inst, c, s)
        assert np.array_equal(q, ev.q[i])
        assert np.array_equal(slack, ev.slack[i])
        assert np.array_equal(crit, ev.critical[i])
        assert np.array_equal(memory_peaks(inst, c, s), ev.peaks[i])
        assert memory_feasible(inst, c, s) == bool(ev.mem_ok[i])
    # the neighborhood must exercise both verdicts for this test to mean much
    assert 0 < n_cyclic < len(cands)


def test_batch_schedule_row_is_interchangeable():
    """BatchEval.schedule(i) feeds the scalar heads_tails unchanged."""
    inst, cands = neighbor_candidates(3, k=8)
    ev = batch_evaluate(inst, cands)
    for i, c in enumerate(cands):
        if not ev.feasible[i]:
            continue
        s_row = ev.schedule(i)
        s_ref = exact_schedule(inst, c)
        out_row = heads_tails(inst, c, s_row)
        out_ref = heads_tails(inst, c, s_ref)
        for a, b in zip(out_row, out_ref):
            assert np.array_equal(a, b)


def test_scalar_engine_matches_numpy_engine():
    inst, cands = neighbor_candidates(4)
    ev_np = BatchEvaluator(inst, backend="numpy").evaluate(cands, tails=True, peaks=True)
    ev_sc = BatchEvaluator(inst, backend="scalar").evaluate(cands, tails=True, peaks=True)
    assert np.array_equal(ev_np.feasible, ev_sc.feasible)
    f = ev_np.feasible
    assert np.array_equal(ev_np.makespan[f], ev_sc.makespan[f])
    assert np.array_equal(ev_np.start[f], ev_sc.start[f])
    assert np.array_equal(ev_np.q[f], ev_sc.q[f])
    assert np.array_equal(ev_np.peaks[f], ev_sc.peaks[f])
    assert np.array_equal(ev_np.mem_ok, ev_sc.mem_ok)


def test_forced_cycle_is_flagged_not_crashed():
    """A machine order contradicting a DAG edge must come back infeasible."""
    inst = random_instance(0, n_tasks=12, n_data=30)
    sol = solve(inst, "greedy:slack_first").solution
    # force a cycle: put v immediately before u on u's machine for a DAG
    # edge u -> v (machine order v -> u  +  precedence u -> v)
    cyc = sol.copy()
    u = int(np.nonzero(np.diff(inst.succ_indptr))[0][0])
    v = int(inst.succs(u)[0])
    cyc.proc_seq[int(cyc.assign[v])].remove(v)
    seq = cyc.proc_seq[int(cyc.assign[u])]
    seq.insert(seq.index(u), v)
    cyc.assign[v] = cyc.assign[u]
    assert exact_schedule(inst, cyc) is None
    ok = sol
    ev = batch_evaluate(inst, [ok, cyc], tails=True, peaks=True)
    assert bool(ev.feasible[0]) and not bool(ev.feasible[1])
    assert np.isinf(ev.makespan[1])
    # infeasible rows must not poison feasibility bookkeeping
    assert bool(ev.mem_ok[1]) is False


def test_pack_solutions_matches_machine_pred_succ():
    inst, cands = neighbor_candidates(5, k=16)
    packed = pack_solutions(inst, cands)
    for i, c in enumerate(cands):
        mp, ms = c.machine_pred_succ(inst.n_tasks)
        assert np.array_equal(mp, packed.mpred[i])
        assert np.array_equal(ms, packed.msucc[i])
        assert np.array_equal(c.assign, packed.assign[i])
        assert np.array_equal(c.mem, packed.mem[i])


def test_bad_backend_rejected():
    inst = random_instance(0, n_tasks=10, n_data=20)
    with pytest.raises(ValueError, match="backend"):
        BatchEvaluator(inst, backend="tpu")


# --------------------------------------------------------------------------- #
# tabu rewiring                                                                #
# --------------------------------------------------------------------------- #
def test_tabu_trajectory_identical_across_numpy_and_scalar_backends():
    """The engine swap must not change the search: same chunked control flow,
    bit-exact evaluations ⇒ identical iterates, evals, and history."""
    inst = random_instance(6, n_tasks=40, n_data=100)
    base = TSParams(max_unimproved=15, time_limit=60.0, top_k=5,
                    max_iters=60, seed=2)
    rep_np = solve(inst, "tabu", params=base)
    rep_sc = solve(inst, "tabu", params=dataclasses.replace(base, backend="scalar"))
    assert rep_np.makespan == rep_sc.makespan
    assert rep_np.iterations == rep_sc.iterations
    assert rep_np.n_exact_evals == rep_sc.n_exact_evals
    assert rep_np.n_approx_evals == rep_sc.n_approx_evals
    assert rep_np.history == rep_sc.history


def test_backend_kwarg_plumbed_through_solve():
    inst = random_instance(7, n_tasks=40, n_data=100)
    rep = solve(inst, "tabu", params=TSParams.fast(seed=1), backend="scalar")
    assert rep.feasible
    rep2 = solve(inst, "tabu", params=TSParams.fast(seed=1))  # default numpy
    assert rep.makespan == rep2.makespan


def test_jax_backend_close_to_numpy():
    pytest.importorskip("jax")
    inst, cands = neighbor_candidates(8, n_tasks=30, n_data=80, k=24)
    ev_np = BatchEvaluator(inst, backend="numpy").evaluate(cands, tails=True)
    ev_jx = BatchEvaluator(inst, backend="jax").evaluate(cands, tails=True)
    assert np.array_equal(ev_np.feasible, ev_jx.feasible)
    f = ev_np.feasible
    np.testing.assert_allclose(ev_jx.makespan[f], ev_np.makespan[f], rtol=1e-5)
    np.testing.assert_allclose(ev_jx.start[f], ev_np.start[f],
                               rtol=1e-5, atol=1e-4 * float(ev_np.makespan[f].max()))
    np.testing.assert_allclose(ev_jx.q[f], ev_np.q[f],
                               rtol=1e-5, atol=1e-4 * float(ev_np.makespan[f].max()))


def test_unavailable_jax_falls_back(monkeypatch):
    import repro.core.eval_batch as eb

    """backend='jax' without JAX is an error, never a silent numpy run."""
    monkeypatch.setattr(eb, "_jax_available", lambda: False)
    inst = random_instance(0, n_tasks=10, n_data=20)
    with pytest.raises(ImportError, match="backend='jax' needs jax"):
        BatchEvaluator(inst, backend="jax")
