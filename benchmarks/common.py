"""Shared benchmark harness: paper-recipe instances at two scales.

Default scale finishes on one CPU in minutes (same generator/ratios as the
paper's Table II, smaller counts + budgets); ``--full`` reproduces the
paper-scale parameters (tasks∈[200,300], data∈[500,700], T=600 s/instance).
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import time

import numpy as np

from repro.core import TSParams, random_instance

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
RESULTS_DIR = os.path.join(REPO_ROOT, "results", "bench")
HISTORY_PATH = os.path.join(RESULTS_DIR, "history.jsonl")


def git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(__file__), capture_output=True, text=True,
            timeout=10, check=True,
        ).stdout.strip()
    except Exception:
        return "unknown"


def append_history(bench: str, gates: dict, **extra) -> str:
    """Append one machine-readable record to ``results/bench/history.jsonl``
    so the perf trajectory is queryable across PRs: git sha, UTC timestamp,
    bench name, and the gate values that run produced."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    record = {
        "sha": git_sha(),
        "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "bench": bench,
        "gates": gates,
        **extra,
    }
    with open(HISTORY_PATH, "a") as f:
        f.write(json.dumps(record) + "\n")
    return HISTORY_PATH


def certify_incumbents(entries, where: str, *,
                       enforce_capacity: bool = True) -> bool:
    """Post-hoc ILP certification of bench incumbents (DESIGN.md §12).

    Runs OUTSIDE every timed section so sanitize mode cannot perturb the
    gated throughput/latency numbers.  ``entries`` is an iterable of
    ``(instance, solution, reported_makespan)`` or
    ``(instance, solution, reported_makespan, claimed_feasible)`` — the
    4th element threads a report's honest feasibility claim so a
    memory-tight instance whose best incumbent is (declaredly) capacity
    infeasible certifies as consistent rather than rejecting.  Returns
    ``True`` (for the gate record's ``certified`` field) after every
    incumbent certifies, ``False`` without checking when sanitize mode is
    off, and raises ``SanitizeError`` on the first bad certificate.
    ``enforce_capacity=False`` records capacity breaches without
    rejecting — for lanes that run with memory updates disabled
    (``MEM_UPDATE_DISABLED``), where incumbents are legitimately
    pre-Alg-3 (DESIGN §12).
    """
    from repro.analysis.sanitize import maybe_sanitize, sanitize_enabled

    if not sanitize_enabled():
        return False
    for entry in entries:
        inst, sol, mk = entry[:3]
        feas = entry[3] if len(entry) > 3 else None
        maybe_sanitize(inst, sol, where=where, flag=True,
                       reported_makespan=mk, claimed_feasible=feas,
                       enforce_capacity=enforce_capacity)
    return True


COMPILE_BUDGET_ENV = "REPRO_COMPILE_BUDGET_S"


def compile_budget_s(default: float = 120.0) -> float:
    """Per-bucket compile-seconds budget from ``REPRO_COMPILE_BUDGET_S``:
    unset → a generous CPU default; ``0``/``off`` disables the gate."""
    raw = os.environ.get(COMPILE_BUDGET_ENV, "").strip().lower()
    if not raw:
        return float(default)
    if raw in ("off", "none", "false", "no"):
        return 0.0
    return float(raw)


def gate_compile_budget(bench: str, seconds_by_bucket: dict):
    """Per-bucket compile-time gate (DESIGN §13: a compile storm is a
    fault mode, not a slow day).  Returns ``(record, breach)``: ``record``
    merges into the bench's history gates; ``breach`` is an error string
    or ``None``.  Callers append history *first*, then raise on breach, so
    a failing run still leaves a queryable record."""
    budget = compile_budget_s()
    vals = {str(k): float(v) for k, v in seconds_by_bucket.items()}
    worst = max(vals.values(), default=0.0)
    ok = budget <= 0.0 or worst <= budget
    record = {"compile_budget_s": budget,
              "compile_worst_bucket_s": round(worst, 3),
              "compile_budget_ok": ok}
    breach = None
    if not ok:
        over = ", ".join(f"{k}={v:.1f}s" for k, v in sorted(vals.items())
                         if v > budget)
        breach = (f"{bench}: per-bucket compile budget {budget:.0f}s "
                  f"exceeded ({over}) — fix the compile storm or raise "
                  f"{COMPILE_BUDGET_ENV}")
    return record, breach


@dataclasses.dataclass(frozen=True)
class Scale:
    n_tasks: tuple[int, int]
    n_data: tuple[int, int]
    n_instances: int
    ts: TSParams

    def instance(self, seed: int, **kw):
        rng = np.random.default_rng(seed)
        kw.setdefault("n_tasks", int(rng.integers(*self.n_tasks)))
        kw.setdefault("n_data", int(rng.integers(*self.n_data)))
        return random_instance(seed, **kw)


def scale(full: bool) -> Scale:
    if full:
        return Scale(
            n_tasks=(200, 301), n_data=(500, 701), n_instances=10,
            ts=TSParams(max_unimproved=100_000, time_limit=600.0, top_k=100),
        )
    return Scale(
        n_tasks=(50, 81), n_data=(120, 181), n_instances=3,
        ts=TSParams(max_unimproved=80, time_limit=8.0, top_k=8),
    )


def emit(name: str, us_per_call: float, derived: str) -> None:
    """The scaffold's CSV contract: name,us_per_call,derived."""
    print(f"{name},{us_per_call:.1f},{derived}")


def device_record() -> dict:
    """The device a result was measured on.  Off a TPU the run is a CPU
    rehearsal: its times and ratios are never speeds."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "rehearsal": devices[0].platform != "tpu"}


def save_json(name: str, payload) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump({"device": device_record(), **payload}, f, indent=1)
    if name.startswith("BENCH"):
        # canonical copy at the repo root: the perf-trajectory tracker scans
        # there, not under results/bench/
        shutil.copyfile(path, os.path.join(REPO_ROOT, f"{name}.json"))
    return path


class Timer:
    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *a):
        self.elapsed = time.monotonic() - self.t0
