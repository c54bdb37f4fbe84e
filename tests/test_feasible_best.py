"""A served best never lies above its start under binding memory capacity.

Between syncs the device search commits moves under the allocation frozen
at the last sync, so a walk's device best can be over capacity.
Algorithm 3 then repairs it at the finish, and the repaired schedule can
be worse than the walk's feasible start.  The driver serves the better of
the repair and the walk's best feasible schedule.

The instances are the benchmark's recipes at rehearsal size
(``bench/configs``: ``fft32`` and ``layered250`` at 20% fast memory,
``layered250_roomy`` at 200%), drawn from seed 16 and stored in
``fixtures/feasible_best_instances.npz``.  On the tight ones below, the
driver that kept every repair served a walk best, and on ``fft8-16-2`` the
request's best, above its start.  ``fixtures/feasible_best_golden.json``
holds that driver's answers on the roomy ones, where no repair happens.
"""
import dataclasses
import json
import pathlib

import pytest

pytest.importorskip("jax")

from repro.analysis.certify import certify_report, certify_solution  # noqa: E402
from repro.core import Budget, TSParams, solve  # noqa: E402
from repro.core.api import multiwalk_inits  # noqa: E402
from repro.core.device_search import DeviceConfig, solve_instances  # noqa: E402
from repro.instances.suites import load_npz  # noqa: E402

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
INSTANCES = {i.name: i for i in load_npz(str(FIXTURES / "feasible_best_instances.npz"))}
GOLDEN = json.loads((FIXTURES / "feasible_best_golden.json").read_text())
WALKS = 4
CRIT_CAP = 64   # every task: one compiled program per shape, whatever the instance


def group(prefix: str) -> list:
    return [i for name, i in INSTANCES.items() if name.startswith(prefix)]


def solve_batch(insts: list, seeds: list, iters: int = 1) -> list:
    """``solve_instances`` as the serve engine runs it: one sync per round."""
    params = dataclasses.replace(TSParams(), max_iters=iters)
    inits = [multiwalk_inits(inst, WALKS, s)[0] for s, inst in zip(seeds, insts)]
    return solve_instances(insts, inits, params,
                           config=DeviceConfig(sync_every=1, crit_cap=CRIT_CAP),
                           seeds=seeds)


def assert_within_start(inst, best, best_mk, initial, walks):
    """``walks``: ``(start makespan, best makespan, best solution)`` each."""
    assert best_mk <= initial
    cert = certify_solution(inst, best, reported_makespan=best_mk,
                            claimed_feasible=True)
    assert cert.ok and not cert.violations, cert.summary()
    for w, (start, mk, sol) in enumerate(walks):
        assert mk <= start, (w, start, mk)
        cert = certify_solution(inst, sol, reported_makespan=mk,
                                claimed_feasible=True)
        assert cert.ok and not cert.violations, (w, cert.summary())


@pytest.mark.parametrize("name, iters, seed", [
    ("fft8-16-2", 2, 2),        # the request's best was served above its start
    ("layered40-16-4", 1, 4),   # a walk's best was served above its start
])
def test_solve_serves_no_best_above_its_start(name, iters, seed):
    inst = INSTANCES[name]
    rep = solve(inst, "tabu_device", budget=Budget(max_iters=iters), seed=seed,
                walks=WALKS, device={"crit_cap": CRIT_CAP})
    cert = certify_report(inst, rep)
    assert cert.ok and not cert.violations, cert.summary()
    assert_within_start(inst, rep.solution, rep.makespan, rep.initial_makespan,
                        [(w["initial_makespan"], w["best_makespan"], w["solution"])
                         for w in rep.extras["per_walk"]])


@pytest.mark.parametrize("prefix, seeds", [
    ("fft8-16", [0, 1, 2, 3]),       # instance 2 was served above its start
    ("layered40-16", [4, 5, 6, 7]),  # instance 4 had a walk above its start
])
def test_solve_instances_serves_no_best_above_its_start(prefix, seeds):
    insts = group(prefix)
    for inst, res in zip(insts, solve_batch(insts, seeds)):
        assert_within_start(inst, res.best, res.best_makespan, res.initial_makespan,
                            [(w.initial_makespan, w.best_makespan, w.best)
                             for w in res.per_walk])


def as_recorded(sol) -> dict:
    return {"assign": [int(x) for x in sol.assign], "mem": [int(x) for x in sol.mem],
            "proc_seq": [[int(t) for t in s] for s in sol.proc_seq]}


@pytest.mark.parametrize("case", GOLDEN["solve"],
                         ids=lambda c: f"{c['instance']}-seed{c['seed']}")
def test_roomy_solve_answers_match_golden(case):
    """Where no walk best is over capacity, every answer is the recorded
    one, to the bit: makespans, orders, cores and tiers."""
    rep = solve(INSTANCES[case["instance"]], "tabu_device", budget=Budget(max_iters=2),
                seed=case["seed"], walks=WALKS, device={"crit_cap": CRIT_CAP})
    assert rep.makespan == case["makespan"]
    assert rep.initial_makespan == case["initial_makespan"]
    assert as_recorded(rep.solution) == case["best"]
    assert [{"best_makespan": w["best_makespan"], "best": as_recorded(w["solution"])}
            for w in rep.extras["per_walk"]] == case["walks"]


def test_roomy_solve_instances_answers_match_golden():
    insts = [INSTANCES[c["instance"]] for c in GOLDEN["batch"]]
    for case, res in zip(GOLDEN["batch"], solve_batch(insts, [0, 1, 2, 3])):
        assert res.best_makespan == case["makespan"]
        assert res.initial_makespan == case["initial_makespan"]
        assert as_recorded(res.best) == case["best"]
        assert [{"best_makespan": w.best_makespan, "best": as_recorded(w.best)}
                for w in res.per_walk] == case["walks"]
