"""The comparison that decides ``correct``.

Every answer due in the window is compared with the plain reference
(``bench.reference``): the served report's schedule and each walk's
incumbent in it.  Five numbers, each against its own limit:

* ``violations``: constraint breaches of the paper's ILP found by the
  reference (assignment, sequencing, allocation, precedence, residency,
  capacity, feasibility claim, claimed makespan), plus reports whose best
  makespan lies above their own initial one.  Exact: limit 0;
* ``makespan_gap``: the widest relative gap between a claimed makespan and
  the reference's float64 makespan of the same schedule.  Its limit lies
  between what sound runs read and what the float32 control reads
  (PERF.md, section 2);
* ``unimproved``: of the answered requests that report at least one
  search iteration, the share whose best makespan is not below their
  initial one (the best of the walks' start schedules after Algorithm
  3); 1 when none reports an iteration.  A request whose whole budget
  went to host preparation reports none and is left out.  A search that
  leaves its state unchanged, whether or not it counts its iterations,
  serves every start back and reads 1; the limit lies between that and
  what sound runs read (PERF.md, section 2);
* ``missing``: requests due in the window (sent before it opened) with no
  answer, or with an error for an answer.  Limit 0;
* ``compiles``: programs lowered inside the window.  Limit 0.

The control puts the reference in the program's place at float32: each
claimed makespan is replaced by the reference's float32 makespan of the
same schedule, and the same comparison and verdict run on it.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import Answer, makespan, violations

__all__ = ["LIMITS", "answers_of", "compare", "control", "verdict"]

LIMITS = {"violations": 0, "makespan_gap": 1e-9, "unimproved": 0.75,
          "missing": 0, "compiles": 0}
IMPROVED = 1e-9      # relative drop below the initial makespan that counts


def answers_of(report) -> list:
    """The schedules in one served report: its best, then each walk's."""
    def ans(sol, mk, feasible):
        return Answer(assign=np.array(sol.assign), mem=np.array(sol.mem),
                      proc_seq=tuple(tuple(int(t) for t in s) for s in sol.proc_seq),
                      makespan=float(mk), feasible=bool(feasible))

    out = [ans(report.solution, report.makespan, report.feasible)]
    for walk in report.extras.get("per_walk", ()):
        # Algorithm 3 repairs every walk incumbent before it is reported
        out.append(ans(walk["solution"], walk["best_makespan"], True))
    return out


def _gap(case, ans: Answer) -> float:
    ref = makespan(case, ans, np.float64)
    return abs(ans.makespan - ref) / ref if ref > 0 else float("inf")


def compare(items, *, missing: int, compiles: int) -> tuple:
    """``items``: ``(case, report_answers, initial_makespan, iterations)``
    per answered request.  Returns each compared number, and up to ten
    lines that say what broke."""
    bad, gap, searched, same = 0, 0.0, 0, 0
    details = []
    for case, answers, initial, iterations in items:
        if iterations > 0:
            searched += 1
            same += not answers[0].makespan < initial * (1 - IMPROVED)
        if answers[0].makespan > initial * (1 + 1e-12):
            bad += 1
            details.append(f"{case.name}: best {answers[0].makespan!r} above "
                           f"initial {initial!r}")
        for ans in answers:
            v = violations(case, ans)
            bad += len(v)
            details += [f"{case.name}: {line}" for line in v[:3]]
            g = _gap(case, ans)
            if gap == gap and not g <= gap:  # a NaN gap is the widest, and stays
                gap = g
    return ({"violations": bad, "makespan_gap": gap,
             "unimproved": same / searched if searched else 1.0,
             "missing": int(missing), "compiles": int(compiles)}, details[:10])


def control(items, *, missing: int = 0, compiles: int = 0) -> tuple:
    """The comparison with the reference at float32 in the program's
    place: each claimed makespan replaced by the float32 one.  Returns
    the numbers and their verdict."""
    items32 = [(case, [dataclasses.replace(a, makespan=makespan(case, a, np.float32))
                       for a in answers], *rest)
               for case, answers, *rest in items]
    numbers, _ = compare(items32, missing=missing, compiles=compiles)
    return numbers, verdict(numbers)


def verdict(numbers: dict) -> bool:
    """True when every number is within its limit (a NaN never is)."""
    return all(numbers[k] <= lim for k, lim in LIMITS.items())
