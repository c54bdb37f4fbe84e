"""The comparison that decides ``correct``: it passes sound schedules and
rejects corrupted ones and the float32 control."""
import json

import numpy as np
import pytest

from bench import check, reference
from bench.instances import draw_pool, shape_class
from bench.spec import ROOT


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def case():
    """One layered250 instance at the configuration's own size."""
    return draw_pool(config("layered250"), 2**31 + 11, 1)[0]


def serial_answer(case):
    """A schedule that is always valid: every task on core 0 in a
    topological order, every block in the unbounded tier."""
    preds = [set() for _ in range(case.n_tasks)]
    for u, v in case.precedence():
        preds[v].add(u)
    order, done = [], set()
    while len(order) < case.n_tasks:
        for t in range(case.n_tasks):
            if t not in done and preds[t] <= done:
                order.append(t)
                done.add(t)
    assign = np.zeros(case.n_tasks, dtype=np.int64)
    mem = np.full(case.n_data, case.n_mems - 1, dtype=np.int64)
    seqs = tuple([tuple(order)] + [()] * (case.n_procs - 1))
    ans = reference.Answer(assign, mem, seqs, 0.0, True)
    mk = reference.makespan(case, ans)
    return reference.Answer(assign, mem, seqs, mk, True)


def program_answer(case):
    """A schedule the program's own search returns, on the CPU."""
    from repro.core import Budget, solve

    from bench.instances import to_program

    rep = solve(to_program(case), "tabu", budget=Budget(max_iters=3), seed=5)
    return check.answers_of(rep), float(rep.initial_makespan), int(rep.iterations)


def numbers(case, answers, initial=float("inf"), iterations=1, **kw):
    kw = {"missing": 0, "compiles": 0, **kw}
    return check.compare([(case, answers, initial, iterations)], **kw)[0]


def test_recipes_draw_the_pinned_shape_class():
    for name in ("layered250", "layered250_roomy", "fft32"):
        cfg = config(name)
        pool = draw_pool(cfg, 17, 3)
        assert [shape_class(c) for c in pool] == [cfg["shape_class"]] * 3


def test_sound_schedules_pass(case):
    got = numbers(case, [serial_answer(case)])
    assert got["violations"] == 0 and got["makespan_gap"] <= 1e-15
    assert check.verdict(got)
    answers, initial, iterations = program_answer(case)
    got = numbers(case, answers, initial)
    assert check.verdict(got), got


@pytest.mark.parametrize("corrupt", [
    "core", "order", "tier", "makespan", "feasibility", "missing", "compile",
    "above_initial",
])
def test_corrupted_answers_fail(case, corrupt):
    answers, initial, iterations = program_answer(case)
    ans = answers[0]
    kw = {}
    if corrupt == "core":       # a task moved to another core's list only
        seqs = [list(s) for s in ans.proc_seq]
        src = next(p for p, s in enumerate(seqs) if s)
        seqs[(src + 1) % len(seqs)].append(seqs[src].pop())
        ans = reference.Answer(ans.assign, ans.mem, tuple(map(tuple, seqs)),
                               ans.makespan, ans.feasible)
    elif corrupt == "order":    # two dependent tasks swapped on one core
        seqs = [list(s) for s in ans.proc_seq]
        edges = {tuple(e) for e in case.precedence().tolist()}
        s, hit = next((s, i) for s in seqs for i in range(len(s) - 1)
                      if (s[i], s[i + 1]) in edges)
        s[hit], s[hit + 1] = s[hit + 1], s[hit]
        ans = reference.Answer(ans.assign, ans.mem, tuple(map(tuple, seqs)),
                               ans.makespan, ans.feasible)
    elif corrupt == "tier":     # every block into the first fast tier
        ans = reference.Answer(ans.assign, np.zeros_like(ans.mem), ans.proc_seq,
                               ans.makespan, ans.feasible)
    elif corrupt == "makespan":
        ans = reference.Answer(ans.assign, ans.mem, ans.proc_seq,
                               ans.makespan * (1 + 1e-6), ans.feasible)
    elif corrupt == "feasibility":
        ans = reference.Answer(ans.assign, ans.mem, ans.proc_seq,
                               ans.makespan, not ans.feasible)
    elif corrupt == "missing":
        kw["missing"] = 1
    elif corrupt == "compile":
        kw["compiles"] = 1
    elif corrupt == "above_initial":
        initial = ans.makespan * 0.99
    got = numbers(case, [ans] + answers[1:], initial, **kw)
    assert not check.verdict(got), got


def test_float32_control_fails_at_the_cells_own_size(case):
    """The reference at float32 in the program's place fails the verdict,
    by a gap far above the limit; float64 reads none."""
    answers, initial, iterations = program_answer(case)
    items = [(case, answers, initial, iterations)]
    got, ok = check.control(items)
    assert ok is False and not check.verdict(got)
    assert got["makespan_gap"] > 3 * check.LIMITS["makespan_gap"]
    assert got["violations"] == 0      # caught by the gap, not the certificate
    answers = answers + [serial_answer(case)]
    assert numbers(case, answers, initial)["makespan_gap"] < check.LIMITS["makespan_gap"]


def test_unimproved_counts_answers_served_at_their_start(case):
    answers, initial, iterations = program_answer(case)
    best = answers[0].makespan
    assert best < initial
    assert numbers(case, answers, initial)["unimproved"] == 0.0
    got = numbers(case, answers, best)          # the start served back
    assert got["unimproved"] == 1.0 and not check.verdict(got)
    # a request that never searched is left out; none searched reads 1
    assert numbers(case, answers, best, iterations=0)["unimproved"] == 1.0
    both = check.compare([(case, answers, best, 0), (case, answers, initial, 3)],
                         missing=0, compiles=0)[0]
    assert both["unimproved"] == 0.0
