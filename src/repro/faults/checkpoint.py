"""Crash-surviving multiwalk search state (DESIGN.md §13).

The device engine's host/launch split gives a natural checkpoint boundary:
between launches, the *entire* walk state — packed sequences, assignments,
memory allocations, tabu tables, counter-based tenure draws and the
threefry key, incumbents, eval/iteration counters — lives in one host
numpy dict, and every launch is a pure function of that dict.  A
:class:`SearchCheckpoint` snapshots it (plus the host-tracked trajectory:
per-walk histories, global incumbent history, crit-bucket and Alg-3
counters, each walk's best capacity-feasible schedule) at a sync
boundary; resuming from the snapshot replays the remaining launches
**bit-identically** — the resumed run's final result
equals the uncrashed run's, field for field, under an iteration/eval
budget (wall-clock fields excepted, and a wall-clock ``time_limit`` stop
is carried over, not restarted: resumed elapsed includes pre-crash
elapsed).

Snapshots are cheap (array copies of one state pytree) and persistence is
atomic (write-temp + ``os.replace``), so a crash mid-save leaves the
previous checkpoint intact.
"""
from __future__ import annotations

import dataclasses
import json
import os
import tempfile
import zlib

import numpy as np

__all__ = [
    "SearchCheckpoint",
    "CheckpointMismatch",
    "instance_fingerprint",
    "params_fingerprint",
    "snapshot",
    "save",
    "load",
]

_VERSION = 2   # 2: each walk's best feasible schedule (``feasible``)


class CheckpointMismatch(ValueError):
    """Resume attempted against a different instance/params/walk shape
    than the checkpoint was taken under."""


def instance_fingerprint(inst) -> int:
    """Order-stable CRC over the instance's defining arrays and counts."""
    h = zlib.crc32(f"{inst.n_tasks}|{inst.n_data}".encode())
    for f in ("task_edges", "producer", "cons_indptr", "cons_idx",
              "in_indptr", "in_idx", "out_indptr", "out_idx",
              "proc_time", "data_size", "mem_cap", "access_time",
              "mem_level", "data_mem_ok"):
        a = np.ascontiguousarray(getattr(inst, f))
        h = zlib.crc32(a.tobytes(), h)
        h = zlib.crc32(str(a.dtype).encode(), h)
    return h


def params_fingerprint(params) -> int:
    """CRC of the search parameters a trajectory depends on (every
    ``TSParams`` field: the repr is stable and total)."""
    return zlib.crc32(repr(params).encode())


@dataclasses.dataclass
class SearchCheckpoint:
    """One sync-boundary snapshot of a ``device_multiwalk`` run."""

    version: int
    instance_fp: int
    params_fp: int
    walks: int
    sync_index: int          # completed sync boundaries before the snapshot
    crit_cap: int            # current critical-set bucket (survives escalation)
    elapsed: float           # wall seconds consumed (budget carry-over)
    n_exact_host: int        # host-side Alg-3 re-evaluations so far
    g_best: float
    init_mk_min: float
    g_hist: list             # [(iteration, makespan)] global incumbent history
    histories: list          # per-walk incumbent histories
    state: dict              # the packed walk-state pytree (numpy copies)
    feasible: dict           # per-walk best feasible rows and makespans


def snapshot(*, instance_fp: int, params_fp: int, walks: int,
             sync_index: int, crit_cap: int, elapsed: float,
             n_exact_host: int, g_best: float, init_mk_min: float,
             g_hist, histories, state: dict,
             feasible: dict) -> SearchCheckpoint:
    """Deep-copy the mutable pieces so later in-place updates by the
    driver cannot bleed into an already-taken checkpoint."""
    return SearchCheckpoint(
        version=_VERSION,
        instance_fp=int(instance_fp), params_fp=int(params_fp),
        walks=int(walks), sync_index=int(sync_index),
        crit_cap=int(crit_cap), elapsed=float(elapsed),
        n_exact_host=int(n_exact_host), g_best=float(g_best),
        init_mk_min=float(init_mk_min),
        g_hist=[(int(i), float(m)) for i, m in g_hist],
        histories=[[(int(i), float(m)) for i, m in h] for h in histories],
        state={k: np.array(v, copy=True) for k, v in state.items()},
        feasible={k: np.array(v, copy=True) for k, v in feasible.items()},
    )


def check_compatible(ckpt: SearchCheckpoint, *, instance_fp: int,
                     params_fp: int, walks: int) -> None:
    if ckpt.version != _VERSION:
        raise CheckpointMismatch(
            f"checkpoint version {ckpt.version} != {_VERSION}")
    if ckpt.instance_fp != instance_fp:
        raise CheckpointMismatch("checkpoint was taken on a different instance")
    if ckpt.params_fp != params_fp:
        raise CheckpointMismatch("checkpoint was taken under different TSParams")
    if ckpt.walks != walks:
        raise CheckpointMismatch(
            f"checkpoint has W={ckpt.walks}, resume requested W={walks}")


def save(ckpt: SearchCheckpoint, path: str) -> str:
    """Atomic persist: numpy arrays verbatim (dtype-preserving), scalars
    and histories as a JSON sidecar inside the same ``.npz``."""
    meta = {k: getattr(ckpt, k) for k in
            ("version", "instance_fp", "params_fp", "walks", "sync_index",
             "crit_cap", "elapsed", "n_exact_host", "g_best", "init_mk_min",
             "g_hist", "histories")}
    arrays = {f"state_{k}": np.asarray(v) for k, v in ckpt.state.items()}
    arrays.update({f"feasible_{k}": np.asarray(v)
                   for k, v in ckpt.feasible.items()})
    d = os.path.dirname(os.path.abspath(path)) or "."
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".ckpt.tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            np.savez(f, meta=np.frombuffer(
                json.dumps(meta).encode(), dtype=np.uint8), **arrays)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return path


def load(path: str) -> SearchCheckpoint:
    with np.load(path) as z:
        meta = json.loads(bytes(np.asarray(z["meta"])).decode())
        state, feasible = {}, {}
        for k in z.files:
            if k.startswith("feasible_"):
                feasible[k[len("feasible_"):]] = np.asarray(z[k])
            if not k.startswith("state_"):
                continue
            v = np.asarray(z[k])
            # 0-d arrays come back as scalars of the original dtype, matching
            # what pack_state builds (np.int64(0), np.bool_(False), ...)
            state[k[len("state_"):]] = v[()] if v.ndim == 0 else v
    meta["g_hist"] = [(int(i), float(m)) for i, m in meta["g_hist"]]
    meta["histories"] = [[(int(i), float(m)) for i, m in h]
                         for h in meta["histories"]]
    return SearchCheckpoint(state=state, feasible=feasible, **meta)
