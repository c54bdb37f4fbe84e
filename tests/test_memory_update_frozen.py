"""Algorithm 3 re-times its refreshes on the frozen machine order.

Inside one ``memory_update`` call only ``mem`` changes: the cores, the
machine orders and so the disjunctive graph stay fixed.  ``FrozenOrder``
keeps that graph in array form, built from the call's first
``exact_schedule``, and every later refresh (the placement loop's and each
pass of the verify-and-evict epilogue) runs through it.  Longest-path
values use only ``max``, which is exact, and one ``start + dur`` add per
task, so any order that respects the graph gives the same bits.  Four
checks hold it there:

* ``FrozenOrder`` against ``exact_schedule`` plus ``heads_tails``, array
  for array, over random allocations and machine orders of the repo's
  graph families and of the benchmark cells' 250-task instances;
* whole allocations and ``ALG3`` counts against those recorded with a
  fresh ``exact_schedule`` and ``heads_tails`` at every refresh
  (``fixtures/alg3_golden.json``), at ``refresh_every`` 1 and 8, on 20
  walk starts of each benchmark cell's configuration and of small
  tight-memory instances; there ``refreshes`` was counted as the
  ``exact_schedule`` calls after a call's first;
* a fast-path call, epilogue included, calls ``exact_schedule`` once;
* ``ALG3["refreshes"]`` reads alike on the fast path and the scalar oracle.
"""
import functools
import importlib
import json
import pathlib

import numpy as np
import pytest

from bench.instances import draw_pool, to_program
from repro.core import random_instance
from repro.core.api import multiwalk_inits
from repro.core.greedy import construct_greedy
from repro.core.memory_update import ALG3, memory_update
from repro.core.solution import FrozenOrder, Solution, exact_schedule, heads_tails
from repro.instances import generators
from repro.instances.suites import load_npz

# the modules, not the functions ``repro.core`` re-exports under their names
ALG3_MODULE = importlib.import_module("repro.core.memory_update")
SOLUTION_MODULE = importlib.import_module("repro.core.solution")

ROOT = pathlib.Path(__file__).resolve().parents[1]
FIXTURES = ROOT / "tests" / "fixtures"
COUNTS = ("calls", "blocks", "refused", "evicted", "refreshes")
# the benchmark cells' configurations, each at a seed of its own, and the
# small tight-memory instances of the fast/scalar oracle test
GOLDEN = {"roomy": ("layered250_roomy", 2**31 + 1801),
          "tight": ("layered250_tight", 2**31 + 1803),
          "small": None}
INSTANCES_PER_CASE, WALKS = 5, 4


def cell_config(name: str) -> dict:
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def golden_instances(case: str) -> list:
    if GOLDEN[case] is None:
        return [random_instance(seed, n_tasks=40, n_data=100, fast_mem_fraction=0.12)
                for seed in range(INSTANCES_PER_CASE)]
    name, seed = GOLDEN[case]
    return [to_program(c) for c in draw_pool(cell_config(name), seed, INSTANCES_PER_CASE)]


@functools.lru_cache(maxsize=None)
def golden_starts(case: str) -> list:
    """(instance, walk start) pairs: each instance's four walk starts as the
    device engine draws them."""
    return [(inst, start) for k, inst in enumerate(golden_instances(case))
            for start in multiwalk_inits(inst, WALKS, k)[0]]


def alg3_record(inst, start, refresh_every: int) -> dict:
    """The allocation ``memory_update`` returns (one digit per block) and
    the ``ALG3`` counts of the call."""
    before = ALG3.copy()
    out = memory_update(inst, start, refresh_every=refresh_every)
    delta = ALG3 - before
    return {"mem": "".join(str(int(m)) for m in out.mem),
            "alg3": {k: int(delta[k]) for k in COUNTS}}


@pytest.mark.parametrize("refresh_every", [1, 8])
@pytest.mark.parametrize("case", sorted(GOLDEN))
def test_allocations_match_the_recorded_ones(case, refresh_every):
    golden = json.loads((FIXTURES / "alg3_golden.json").read_text())
    want = golden[case][str(refresh_every)]
    got = [alg3_record(inst, start, refresh_every)
           for inst, start in golden_starts(case)]
    assert len(got) == len(want) >= 20
    assert got == want


# --------------------------------------------------------------------------- #
# the frozen order against exact_schedule + heads_tails                        #
# --------------------------------------------------------------------------- #
def fixture_instance(name: str):
    return next(i for i in load_npz(str(FIXTURES / "greedy_golden_instances.npz"))
                if i.name == name)


def cell_instance(config: str):
    return to_program(draw_pool(cell_config(config), 2**31 + 1907, 1)[0])


FAMILIES = {
    "layered": lambda: random_instance(3, n_tasks=60, n_data=150, fast_mem_fraction=0.12),
    "in_tree": lambda: generators.in_tree(np.random.default_rng(4), n_tasks=63),
    "out_tree": lambda: generators.out_tree(np.random.default_rng(5), n_tasks=63),
    "fft": lambda: generators.fft(np.random.default_rng(6), width=8),
    "fractional": lambda: fixture_instance("tight40_nonint"),
    "roomy250": lambda: cell_instance("layered250_roomy"),
    "tight250": lambda: cell_instance("layered250_tight"),
}


def random_order(inst, rng, cores=None) -> Solution:
    """A random topological order of the DAG dealt to random compatible
    cores (``cores`` limits the choice), each core keeping the order."""
    indeg = np.diff(inst.pred_indptr).tolist()
    ready = [i for i in range(inst.n_tasks) if indeg[i] == 0]
    seq = [[] for _ in range(inst.n_procs)]
    assign = np.zeros(inst.n_tasks, dtype=np.int64)
    while ready:
        u = ready.pop(int(rng.integers(len(ready))))
        ok = inst.compatible_procs(u)
        if cores is not None:
            ok = np.intersect1d(ok, cores)
        assign[u] = rng.choice(ok)
        seq[assign[u]].append(u)
        for v in inst.succs(u):
            indeg[v] -= 1
            if indeg[v] == 0:
                ready.append(int(v))
    return Solution(assign=assign, mem=np.zeros(inst.n_data, dtype=np.int64), proc_seq=seq)


def sparse_order(inst, rng) -> Solution:
    """Every task on core 0 but one, alone on the last core it may use; the
    cores between stay empty."""
    sol = random_order(inst, rng, cores=[0])
    u = int(rng.choice(np.nonzero(np.isfinite(inst.proc_time[:, 1:]).any(axis=1))[0]))
    last = int(inst.compatible_procs(u)[-1])
    sol.proc_seq[0].remove(u)
    sol.proc_seq[last].append(u)
    sol.assign[u] = last
    return sol


def random_allocation(inst, rng) -> np.ndarray:
    """A compatible tier per block, drawn at random (capacity ignored)."""
    pick = rng.random((inst.n_data, inst.n_mems)) * inst.data_mem_ok
    return np.argmax(pick, axis=1)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_frozen_order_times_like_the_reference(family):
    inst = FAMILIES[family]()
    rng = np.random.default_rng(sorted(FAMILIES).index(family))
    sols = ([construct_greedy(inst, "slack_first", rng=0), sparse_order(inst, rng)]
            + [random_order(inst, rng) for _ in range(3)])
    assert not all(sols[1].proc_seq[1:-1])           # empty cores
    assert min(len(s) for s in sols[1].proc_seq if s) == 1   # a one-task core
    for sol in sols:
        frozen = FrozenOrder(inst, sol, exact_schedule(inst, sol))
        for mem in [sol.mem] + [random_allocation(inst, rng) for _ in range(4)]:
            sol.mem = mem
            want = exact_schedule(inst, sol)
            got = frozen.schedule(mem)
            assert np.array_equal(got.start, want.start)
            assert np.array_equal(got.finish, want.finish)
            assert got.makespan == want.makespan
            for a, b in zip(frozen.heads_tails(got), heads_tails(inst, sol, want)):
                assert a.dtype == b.dtype and np.array_equal(a, b)


# --------------------------------------------------------------------------- #
# one exact_schedule per fast call, and the refresh counter                    #
# --------------------------------------------------------------------------- #
def count_exact_schedules(monkeypatch) -> list:
    calls = []
    real = SOLUTION_MODULE.exact_schedule

    def counted(inst, sol):
        calls.append(1)
        return real(inst, sol)

    for module in (ALG3_MODULE, SOLUTION_MODULE):
        monkeypatch.setattr(module, "exact_schedule", counted)
    return calls


# a cell start whose epilogue evicts at both refresh intervals
EVICTING = ("tight", 2)


@pytest.mark.parametrize("refresh_every", [1, 8])
def test_a_fast_call_runs_one_exact_schedule(refresh_every, monkeypatch):
    inst, start = golden_starts(EVICTING[0])[EVICTING[1]]
    calls = count_exact_schedules(monkeypatch)
    before = ALG3.copy()
    memory_update(inst, start, refresh_every=refresh_every)
    delta = ALG3 - before
    assert delta["evicted"] > 0 and delta["refreshes"] > delta["evicted"]
    assert len(calls) == 1


@pytest.mark.parametrize("case", ["small", "tight"])
@pytest.mark.parametrize("refresh_every", [1, 8])
def test_refreshes_count_alike_on_both_paths(case, refresh_every, monkeypatch):
    """The scalar oracle re-times with ``exact_schedule`` at the same points,
    so there the count is its calls after the first."""
    inst, start = golden_starts(case)[2]
    calls = count_exact_schedules(monkeypatch)
    deltas = []
    for scalar in (False, True):
        before = ALG3.copy()
        memory_update(inst, start, refresh_every=refresh_every, scalar=scalar)
        deltas.append(ALG3 - before)
    assert deltas[0] == deltas[1]
    assert deltas[0]["refreshes"] > 0
    assert deltas[0]["refreshes"] == len(calls) - 2    # one first call per path
