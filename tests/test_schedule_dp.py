"""Pallas/XLA schedule-DP sweep kernels: interpret-mode parity with the
NumPy engine on start/finish/feasible/Q, across bucket-boundary task counts
and mixed acyclic/cyclic candidate batches."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax import enable_x64  # noqa: E402

from repro.core import random_instance  # noqa: E402
from repro.core.eval_batch import BatchEvaluator, pack_solutions  # noqa: E402
from repro.core.greedy import construct_greedy  # noqa: E402
from repro.core.solution import exact_schedule, heads_tails  # noqa: E402
from repro.core.tabu import _cc_moves, _n7_moves, apply_move  # noqa: E402
from repro.kernels import schedule_dp as sdp  # noqa: E402


def candidate_batch(seed, n_tasks, n_data=90, max_k=24):
    """A mixed feasible/cyclic candidate batch from a real neighborhood."""
    inst = random_instance(seed, n_tasks=n_tasks, n_data=n_data)
    sol = construct_greedy(inst, "slack_first", rng=seed)
    sched = exact_schedule(inst, sol)
    r, q, _, crit = heads_tails(inst, sol, sched)
    moves = _n7_moves(sol, crit) + _cc_moves(inst, sol, crit, r, sched.start, 5)
    cands = [sol]
    for m in moves[: max_k - 1]:
        c = sol.copy()
        apply_move(c, m)
        cands.append(c)
    return inst, cands


def reference(inst, cands):
    eng = BatchEvaluator(inst)
    packed = pack_solutions(inst, cands)
    # ev.q is the production backward sweep over finish - start (the scalar
    # heads_tails operands) — the sweeps must match THAT, not a raw-dur Q
    ev = eng.evaluate(packed, tails=True)
    dur = eng._durations(packed)
    return packed, dur, ev, ev.q


def run_sweep(inst, packed, dur, impl):
    import jax.numpy as jnp

    g = sdp.dense_graph(inst)
    n, n_b, k = inst.n_tasks, g.n_b, packed.k

    def pad(a, fill, dt):
        out = np.full((k, n_b), fill, dtype=dt)
        out[:, :n] = a
        return out

    with enable_x64():
        start, finish, level, n_done, q = sdp.sweep(
            g,
            jnp.asarray(pad(dur, 0.0, np.float64)),
            jnp.asarray(pad(packed.mpred, -1, np.int64)),
            jnp.asarray(pad(packed.msucc, -1, np.int64)),
            impl=impl,
        )
        return (np.asarray(start)[:, :n], np.asarray(finish)[:, :n],
                np.asarray(level)[:, :n], np.asarray(n_done) == n,
                np.asarray(q)[:, :n])


# bucket quantum is 32: exactly at, one under, one over the boundary, plus
# the next bucket's edge cases
@pytest.mark.parametrize("n_tasks", [31, 32, 33, 63, 64, 65])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_sweep_parity_at_bucket_edges(n_tasks, impl):
    inst, cands = candidate_batch(n_tasks % 7, n_tasks)
    packed, dur, ev, q_ref = reference(inst, cands)
    start, finish, level, feasible, q = run_sweep(inst, packed, dur, impl)
    assert np.array_equal(feasible, ev.feasible)
    assert (~feasible).sum() > 0 or n_tasks < 40  # batches usually mix in cycles
    f = ev.feasible
    assert np.array_equal(start[f], ev.start[f])
    assert np.array_equal(finish[f], ev.finish[f])
    assert np.array_equal(level[f], ev.level[f])
    assert np.array_equal(q, q_ref)


def test_bucket_rounds_up_to_quantum():
    assert sdp.bucket(1) == 32
    assert sdp.bucket(32) == 32
    assert sdp.bucket(33) == 64
    assert sdp.bucket(65) == 96


def test_dense_graph_matches_csr():
    inst = random_instance(3, n_tasks=40, n_data=90)
    g = sdp.dense_graph(inst)
    for t in range(inst.n_tasks):
        preds = sorted(int(x) for x in inst.preds(t))
        dense = sorted(int(x) for x in g.pred_mat[t] if x >= 0)
        assert preds == dense
        assert sorted(np.nonzero(g.adj[t, : inst.n_tasks])[0].tolist()) == preds


def test_eval_batch_jax_backend_pallas_interpret_route():
    """The jax backend with jax_impl='pallas_interpret' must agree with the
    NumPy engine verdict-for-verdict (float tolerance on f32)."""
    inst, cands = candidate_batch(2, 40)
    ref = BatchEvaluator(inst, backend="numpy").evaluate(cands)
    eng = BatchEvaluator(inst, backend="jax", jax_impl="pallas_interpret")
    ev = eng.evaluate(cands)
    assert np.array_equal(ev.feasible, ref.feasible)
    f = ref.feasible
    assert np.allclose(ev.makespan[f], ref.makespan[f], rtol=1e-5)
    info = eng.cache_info()
    assert info["misses"] == 1 and info["currsize"] == 1
    eng.evaluate(cands)
    assert eng.cache_info()["hits"] == 1
