"""repro — HDATS (Ding et al., 2022): data allocation and task scheduling on
heterogeneous multiprocessors under memory constraints, served as a
memory-constrained DAG scheduler on the TPU.

The supported solver surface lives here::

    from repro import solve, Budget

    report = solve(instance, method="tabu", budget=Budget(time_limit=10.0))
    report.makespan, report.solution, report.history

Heavy subsystems (``repro.plan``, ``repro.kernels``, ``repro.serve``, …)
import JAX and are deliberately *not* pulled in by this module; import them
explicitly.
"""
from .core.api import (
    Budget,
    Callbacks,
    SolveReport,
    Solver,
    get_solver,
    list_solvers,
    register_solver,
    solve,
)

__all__ = [
    "Budget",
    "Callbacks",
    "SolveReport",
    "Solver",
    "solve",
    "register_solver",
    "get_solver",
    "list_solvers",
]
