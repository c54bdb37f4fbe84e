"""Device-resident multi-walk tabu search — the whole round loop as one
``jax.jit``-compiled program.

PR 3 made every tabu *iteration* array-shaped, but the driver still
ping-pongs between Python and the evaluator each round.  This engine ports
the full multiwalk round — N7/change-core move generation, the batched
approximate window kernel, tabu-table/aspiration updates, chunked top-K
exact evaluation (via ``repro.kernels.schedule_dp``), commit, and incumbent
tracking — into a single jitted ``lax.while_loop`` body over the packed
``(W, …)`` state.  A whole budget of rounds runs with zero host round-trips
except periodic incumbent readback every ``sync_every`` rounds (where wall
time is checked and, when enabled, Algorithm 3 re-allocates memory).

Static-shape discipline:

* ``n_tasks``/``n_procs``/``seq_len``/edge counts are padded to **shape
  buckets** (``schedule_dp.bucket``), so recompiles are bounded and a batch
  of same-bucket instances shares one compiled program;
* the per-round neighborhood is laid out at a fixed capacity derived from a
  **critical-set bucket** ``crit_cap``: rounds whose critical set overflows
  it set an overflow flag, the launch returns early without committing the
  round, and the host relaunches with the next bucket (escalation is
  geometric, so at most O(log n) recompiles per run);
* compiled launches live in a bounded LRU keyed on the bucket tuple
  (``launch_cache_info()``), and the state pytree is **donated** to each
  launch, so a run owns one set of device buffers.

Parity contract (asserted by ``tests/test_device_search.py`` and the
``search_bench`` device lane): with ``W=1``, float64 (the engine always
traces under ``jax.enable_x64``), and ``mem_update_period``
large enough that Algorithm 3 never fires inside the horizon, the engine's
trajectory — history, incumbent, iteration and eval counts — is
**bit-for-bit identical** to the legacy ``tabu_search`` / ``tabu_multiwalk``
drivers on the numpy backend, as long as the trajectory never enters the
perturbation branch.  This holds because every float op replays the numpy
engine's operand set and order: the engine multiplies no floats (block
size × access time comes precomputed in ``InstancePack.io_cost``, so XLA
cannot contract a multiply and an add into one fused multiply-add), max
reductions are order-independent,
durations replay the global cumsum-difference via a blocked *sequential*
scan (``jnp.cumsum`` does NOT match ``np.cumsum`` bitwise — measured, not
assumed), approximate-window sums replay the scalar left-to-right order,
tie-breaks use stable sorts over the scalar enumeration order, and tabu
tenures are counter-based draws (``tabu._tenure_draw``) replayed in uint32.
Divergence points are explicit: the perturbation branch draws from an
on-device threefry stream (one random move per stalled round instead of the
legacy ``perturbation_size`` chain), and Algorithm 3 is amortized to sync
boundaries instead of per accepted move.

``solve_instances`` vmaps the engine over a batch of same-bucket instances
so ``benchmarks/search_bench.py`` / ``paper_tables.py`` can evaluate an
entire Table-II row in one compiled call; per-instance trajectories are
identical to per-instance runs because every loop update is masked.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time

import numpy as np

from .eval_batch import APPROX_WINDOW, LRUCache
from .mdfg import Instance
from .memory_update import memory_update
from .solution import _EPS, Solution, exact_schedule
from .tabu import MultiWalkResult, TSEvent, TSParams, WalkInfo, _maybe_sanitize

__all__ = [
    "DeviceConfig",
    "MEM_UPDATE_DISABLED",
    "REPAIRS",
    "device_multiwalk",
    "solve_instances",
    "warm_launches",
    "launch_cache_info",
]

# mem_update_period at or above this disables Algorithm 3 inside the search
# (the parity profile); below it, the device engine amortizes Alg-3 to sync
# boundaries instead of running it per accepted move.
MEM_UPDATE_DISABLED = 1 << 30

_I32 = np.int32
_NONE = np.int64(1 << 62)  # "unbounded" sentinel for budget axes


@dataclasses.dataclass(frozen=True)
class DeviceConfig:
    """Launch shape/behavior knobs (everything here is compile-relevant)."""

    sync_every: int = 64          # rounds per jit launch (readback cadence)
    crit_cap: int | None = None   # critical-set capacity; None = auto bucket
    donate: bool = True           # donate the state pytree to each launch
    perturb: bool = True          # threefry random move on stalled rounds


# sized for serving traffic: a few signature classes × quantized batch
# sizes plus solo (batch=0) baselines must coexist without thrashing
_LAUNCHES = LRUCache(maxsize=16)

# crit-bucket overflow→relaunch escalations since process start.  Each one
# costs a fresh jit compile mid-run; the serve engine and the benches read
# deltas of this counter so compile storms under traffic are observable
# instead of silent.
_OVERFLOW_RELAUNCHES = 0


def launch_cache_info() -> dict:
    """Compiled-launch cache counters
    (`{hits, misses, evictions, currsize, maxsize, overflow_relaunches}`)."""
    info = _LAUNCHES.info()
    info["overflow_relaunches"] = _OVERFLOW_RELAUNCHES
    return info


def _note_overflow_relaunch() -> None:
    global _OVERFLOW_RELAUNCHES
    _OVERFLOW_RELAUNCHES += 1


REPAIRS: "collections.Counter[str]" = collections.Counter()
"""Walk bests served with Algorithm 3 on (``walks``); of them, those whose
device best was over capacity (``infeasible``); and of those, the walks
served from their best feasible schedule because the repaired best came out
worse (``fallback``)."""
_REPAIRS_LOCK = threading.Lock()   # the serve engine solves on several threads


# --------------------------------------------------------------------------- #
# instance packing — lives in repro.instances.batch (PR 5); re-exported here   #
# because the packed form was born in this module and tests/benchmarks         #
# imported it from here                                                        #
# --------------------------------------------------------------------------- #
from ..instances.batch import (  # noqa: E402
    InstanceBatch,
    InstancePack,
    ia_from_pack,
    pack_instance,
)


# --------------------------------------------------------------------------- #
# state packing                                                                #
# --------------------------------------------------------------------------- #
def _fill_seq_rows(sol: Solution, seq_row, seq_len_row, mpred_row,
                   msucc_row) -> None:
    """Write one walk's padded sequences + machine links from a Solution."""
    for pp, s in enumerate(sol.proc_seq):
        seq_len_row[pp] = len(s)
        if s:
            seq_row[pp, : len(s)] = s
            arr = np.asarray(s, dtype=_I32)
            if len(arr) >= 2:
                mpred_row[arr[1:]] = arr[:-1]
                msucc_row[arr[:-1]] = arr[1:]


def pack_state(ip: InstancePack, sols: list[Solution], scheds,
               seed: int) -> dict:
    """Walk state pytree (host numpy; becomes device-resident on launch)."""
    w = len(sols)
    seq = np.full((w, ip.p_b, ip.s_b), -1, dtype=_I32)
    seq_len = np.zeros((w, ip.p_b), dtype=_I32)
    assign = np.zeros((w, ip.n_b), dtype=_I32)
    mem = np.zeros((w, ip.d_b), dtype=_I32)
    mpred = np.full((w, ip.n_b), -1, dtype=_I32)
    msucc = np.full((w, ip.n_b), -1, dtype=_I32)
    start = np.zeros((w, ip.n_b))
    finish = np.zeros((w, ip.n_b))
    for i, (sol, sched) in enumerate(zip(sols, scheds)):
        assign[i, : ip.n] = sol.assign
        mem[i, : ip.d] = sol.mem
        _fill_seq_rows(sol, seq[i], seq_len[i], mpred[i], msucc[i])
        start[i, : ip.n] = sched.start
        finish[i, : ip.n] = sched.finish
    cur_mk = np.array([s.makespan for s in scheds])
    return {
        "seq": seq, "seq_len": seq_len, "assign": assign, "mem": mem,
        "mpred": mpred, "msucc": msucc, "start": start, "finish": finish,
        "cur_mk": cur_mk, "best_mk": cur_mk.copy(),
        "best_seq": seq.copy(), "best_seq_len": seq_len.copy(),
        "best_assign": assign.copy(), "best_mem": mem.copy(),
        "tabu": np.full((w, ip.n_b * ip.p_b * (ip.n_b + 2)), -1, dtype=_I32),
        "unimproved": np.zeros(w, dtype=_I32),
        "accepted": np.zeros(w, dtype=_I32),
        "active": np.ones(w, dtype=bool),
        "it": np.int64(0),
        "n_exact": np.int64(0),
        "n_approx": np.int64(0),
        "n_perturb": np.int64(0),
        "stop": np.bool_(False),       # max_evals tripped mid-round
        "overflow": np.bool_(False),   # crit set exceeded crit_cap
        "key": np.asarray([seed & 0xFFFFFFFF, 0x6A09E667], dtype=np.uint32),
        "seed": np.uint32(seed & 0xFFFFFFFF),
    }


def unpack_solution(ip: InstancePack, seq, seq_len, assign, mem, w: int) -> Solution:
    proc_seq = [
        [int(t) for t in seq[w, pp, : int(seq_len[w, pp])]]
        for pp in range(ip.p)
    ]
    return Solution(assign=np.asarray(assign[w, : ip.n], dtype=np.int64).copy(),
                    mem=np.asarray(mem[w, : ip.d], dtype=np.int64).copy(),
                    proc_seq=proc_seq)


# --------------------------------------------------------------------------- #
# jitted launch                                                                #
# --------------------------------------------------------------------------- #
def _seq_cumsum(v, block: int = 128):
    """Exclusive-to-inclusive prefix sums replaying ``np.cumsum``'s
    left-to-right order exactly (a scan over blocks whose bodies unroll the
    sequential adds).  Returns ``(rows, e + 1)`` with a leading zero column,
    exactly like the numpy engine's cumsum-difference scaffold."""
    import jax
    import jax.numpy as jnp

    rows, e = v.shape
    assert e % block == 0
    chunks = jnp.moveaxis(v.reshape(rows, e // block, block), 1, 0)

    def body(carry, chunk):
        outs = []
        for jj in range(block):
            carry = carry + chunk[:, jj]
            outs.append(carry)
        return carry, jnp.stack(outs, axis=1)

    _, outs = jax.lax.scan(body, jnp.zeros((rows,), v.dtype), chunks)
    c = jnp.moveaxis(outs, 0, 1).reshape(rows, e)
    return jnp.concatenate([jnp.zeros((rows, 1), v.dtype), c], axis=1)


def _mix32_jnp(jnp, *words):
    h = jnp.uint32(0x811C9DC5)
    for wd in words:
        h = h ^ jnp.asarray(wd).astype(jnp.uint32)
        h = h * jnp.uint32(0x9E3779B1)
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
    return h


def _take_w(arr2d, idx):
    """arr2d (W, n), idx (W, ...) → gathered values per walk."""
    import jax.numpy as jnp

    flat = idx.reshape(arr2d.shape[0], -1)
    return jnp.take_along_axis(arr2d, flat, axis=1).reshape(idx.shape)


def _new_seq_at(seq_dst, u, j, k, cc, i):
    """Element ``i`` of each move's post-move destination sequence
    (``eval_batch._new_seq_at`` verbatim)."""
    import jax.numpy as jnp

    t = i - (i > j)
    orig = t + ((~cc) & (t >= k))
    g = jnp.take_along_axis(
        seq_dst, jnp.clip(orig, 0, seq_dst.shape[-1] - 1)[..., None],
        axis=-1)[..., 0]
    return jnp.where(i == j, u, g)


def _window_estimates(ia: dict, seq, seq_len, mem, dur_all, r_all, q_all,
                      mv: dict):
    """The approximate evaluation of every move: ``(est, finite)``, (W, M).

    ``mv`` holds each move's ``task``, ``src_s``, ``dst_p``, ``dst_s``,
    ``cc`` and ``valid`` (W, M), with masked slots holding in-range indices.
    Heads are recomputed along the move's window of the destination
    sequence (old heads elsewhere), and ``est`` is the largest new head plus
    old tail over the window; ``finite`` is whether the moved task's
    duration on its destination core is.

    What a move reads of a task does not depend on the move, so it is
    tabulated once per call and read per move as a row: each task's
    predecessors' durations and old finishes ``(W, n_b, Dp)``, and each
    (task, core)'s re-priced duration ``(W, n_b, p_b)``.  A predecessor is
    in the window if it equals a task placed at an earlier step of the same
    move.  Every add and max is the scalar oracle's, in its order.
    """
    import jax.numpy as jnp

    pred_mat, proc_time, io_cost = ia["pred_mat"], ia["proc_time"], ia["io_cost"]
    u, k, b, j, cc, valid = (mv[key] for key in
                             ("task", "src_s", "dst_p", "dst_s", "cc", "valid"))
    W, n_b, s_b = seq.shape[0], proc_time.shape[0], seq.shape[2]
    n_m = io_cost.shape[2]

    def rows(tab, x):
        """tab (W, n_b, D), x (W, M) → each move's row (W, M, D)."""
        return jnp.take_along_axis(tab, x[:, :, None], axis=1)

    def reprice(blk_mat):
        """Move-in or move-out time of every (walk, task, core) under the
        walk's allocation: the task's blocks priced on the core, added left
        to right over the zero-padded width (the scalar order)."""
        ok = blk_mat >= 0
        bsafe = jnp.where(ok, blk_mat, 0)                     # (n_b, L)
        memv = mem[:, bsafe][:, :, None, :]                   # (W, n_b, 1, L)
        per_tier = jnp.moveaxis(io_cost[bsafe], 1, 2)         # (n_b, p_b, L, n_m)
        cost = per_tier[None, ..., 0]
        for m in range(1, n_m):
            cost = jnp.where(memv == m, per_tier[None, ..., m], cost)
        vals = jnp.where(ok[None, :, None, :], cost, 0.0)     # (W, n_b, p_b, L)
        tot = jnp.zeros(vals.shape[:3], jnp.float64)
        for jj in range(vals.shape[3]):
            tot = tot + vals[..., jj]
        return tot

    pclip = jnp.clip(pred_mat, 0, n_b - 1)
    pred_dur = dur_all[:, pclip]                              # (W, n_b, Dp)
    pred_fin = r_all[:, pclip] + pred_dur
    d_tab = reprice(ia["in_blk"]) + proc_time[None] + reprice(ia["out_blk"])

    seq_dst = jnp.take_along_axis(seq, b[:, :, None], axis=1)    # (W, M, s_b)
    dur_old = _take_w(dur_all, u)
    q_old = _take_w(q_all, u)
    d_cc = _take_w(d_tab.reshape(W, -1), u * proc_time.shape[1] + b)
    dur_u = jnp.where(cc, d_cc, dur_old)
    q_u = jnp.where(cc, q_old - dur_old + d_cc, q_old)
    finite = jnp.isfinite(dur_u)
    new_len = jnp.take_along_axis(seq_len, b, axis=1) + cc
    w_lo = jnp.where(cc, j, jnp.minimum(k, j))
    w_hi = jnp.minimum(new_len, w_lo + APPROX_WINDOW)
    est = jnp.zeros(u.shape, jnp.float64)
    xp = jnp.take_along_axis(
        seq_dst, jnp.clip(w_lo - 1, 0, s_b - 1)[..., None], axis=2)[..., 0]
    xp = jnp.clip(xp, 0, n_b - 1)
    prev_finish = jnp.where(
        w_lo > 0, _take_w(r_all, xp) + _take_w(dur_all, xp), 0.0)
    placed = []    # (task, new head) of each earlier window step
    for s in range(APPROX_WINDOW):
        idxp = w_lo + s
        act = valid & (idxp < w_hi)
        x = jnp.where(act, _new_seq_at(seq_dst, u, j, k, cc, idxp), 0)
        preds = pred_mat[x]                                   # (W, M, Dp)
        # the active steps of a move are a prefix of its window, so an
        # active step meets only active ones; the latest placement counts
        in_win = jnp.zeros(preds.shape, bool)
        head_at = jnp.zeros(preds.shape, jnp.float64)
        for xt, ht in placed:
            hit = preds == xt[..., None]
            in_win = in_win | hit
            head_at = jnp.where(hit, ht[..., None], head_at)
        dsel = jnp.where(preds == u[..., None], dur_u[..., None],
                         rows(pred_dur, x))
        f = jnp.where(preds >= 0,
                      jnp.where(in_win, head_at + dsel, rows(pred_fin, x)),
                      -jnp.inf)
        head = jnp.maximum(prev_finish, f.max(axis=2))
        placed.append((x, head))
        is_u = x == u
        dx = jnp.where(is_u, dur_u, _take_w(dur_all, x))
        qx = jnp.where(is_u, q_u, _take_w(q_all, x))
        est = jnp.where(act, jnp.maximum(est, head + qx), est)
        prev_finish = jnp.where(act, head + dx, prev_finish)
    tailm = valid & (w_hi < new_len)
    x_t = _new_seq_at(seq_dst, u, j, k, cc, w_hi)
    x_t = jnp.clip(jnp.where(tailm, x_t, 0), 0, n_b - 1)
    est = jnp.where(tailm, jnp.maximum(est, prev_finish + _take_w(q_all, x_t)),
                    est)
    return jnp.where(finite & valid, est, jnp.inf), finite


def _round_loop(ia: dict, w_count: int, params: TSParams,
                crit_cap: int, rounds: int, cfg: DeviceConfig):
    """Build the ``rounds``-bounded while_loop over full tabu rounds.

    ``ia`` holds the (possibly traced) instance arrays; every static shape
    is read off them, so the same body traces for one instance (arrays as
    constants) or under ``vmap`` (arrays as batched tracers).  Returns
    ``run(state, series)``.

    The device ops carry named scopes, which change op metadata only:
    ``ts_round`` around the loop and, inside a round, ``ts_move_gen``,
    ``ts_approx_eval``, ``ts_exact_eval``, ``ts_perturb`` and ``ts_commit``.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import schedule_dp as sdp

    ia = {k: jnp.asarray(v) for k, v in ia.items()}  # no-op on tracers
    pred_mat = ia["pred_mat"]
    succ_mat = ia["succ_mat"]
    in_idx = ia["in_idx"]
    in_owner = ia["in_owner"]
    in_valid = ia["in_valid"]
    in_ptr = ia["in_ptr"]
    out_idx = ia["out_idx"]
    out_owner = ia["out_owner"]
    out_valid = ia["out_valid"]
    out_ptr = ia["out_ptr"]
    proc_time = ia["proc_time"]
    io_cost = ia["io_cost"]          # data_size x access_time, host-formed
    compat = ia["compat"]
    n = ia["n"]                      # real sizes: scalars, traced in batch
    p = ia["p"]
    n_b, p_b = proc_time.shape
    s_b = n_b + 1
    d_b = io_cost.shape[0]
    W, C, K = w_count, crit_cap, params.top_k
    NPOS = params.n_change_core_positions
    M_n7 = 2 * C
    M_cc = C * p_b * (NPOS + 1)
    M = M_n7 + M_cc
    R = rounds
    max_unimp = params.max_unimproved
    max_iters = _NONE if params.max_iters is None else np.int64(params.max_iters)
    max_evals = _NONE if params.max_evals is None else np.int64(params.max_evals)

    wi = jnp.arange(W)
    INF = jnp.inf

    def durations(assign_rows, mem_rows):
        """``solution.durations`` replayed bit-exactly per row: global
        sequential cumsum over the CSR edge values, then indptr differences."""
        def io_time(idx, owner, valid, ptr):
            cost = io_cost[idx[None, :], assign_rows[:, owner],
                           mem_rows[:, idx]]
            vals = jnp.where(valid[None, :], cost, 0.0)
            c = _seq_cumsum(vals)
            return c[:, ptr[1:]] - c[:, ptr[:-1]]

        t_in = io_time(in_idx, in_owner, in_valid, in_ptr)
        t_out = io_time(out_idx, out_owner, out_valid, out_ptr)
        pt = proc_time[jnp.arange(n_b)[None, :], assign_rows]
        return t_in + pt + t_out

    def eval_candidates(assign_c, mpred_c, mem_rows):
        """Exact DP on (rows, n_b) candidate rows: durations + forward sweep."""
        dur = durations(assign_c, mem_rows)
        start, finish, _, n_done, _ = sdp.sweep_xla(
            pred_mat, succ_mat, dur, mpred_c,
            jnp.full_like(mpred_c, -1), n, tails=False)
        feasible = n_done == n
        valid_col = (jnp.arange(n_b) < n)[None, :]
        mk = jnp.where(feasible,
                       jnp.where(valid_col, finish, -INF).max(axis=1), INF)
        return start, finish, feasible, mk

    def seq_positions(seq, seq_len):
        """(mach, pos) (W, n_b) from the padded sequence tensor."""
        col = jnp.arange(s_b)[None, None, :]
        validp = col < seq_len[:, :, None]
        t_safe = jnp.where(validp, seq, n_b)
        mach = jnp.full((W, n_b + 1), -1, _I32)
        pos = jnp.full((W, n_b + 1), -1, _I32)
        pvals = jnp.broadcast_to(jnp.arange(p_b, dtype=_I32)[None, :, None],
                                 (W, p_b, s_b))
        svals = jnp.broadcast_to(jnp.arange(s_b, dtype=_I32)[None, None, :],
                                 (W, p_b, s_b))
        w3 = jnp.broadcast_to(wi[:, None, None], (W, p_b, s_b))
        mach = mach.at[w3, t_safe].set(pvals)
        pos = pos.at[w3, t_safe].set(svals)
        return mach[:, :n_b], pos[:, :n_b]

    def links_from_seq(seq, seq_len):
        col = jnp.arange(s_b)[None, None, :]
        validp = col < seq_len[:, :, None]
        t_safe = jnp.where(validp, seq, n_b)
        w3 = jnp.broadcast_to(wi[:, None, None], (W, p_b, s_b - 1))
        mp = jnp.full((W, n_b + 1), -1, _I32)
        ms = jnp.full((W, n_b + 1), -1, _I32)
        mp = mp.at[w3, t_safe[:, :, 1:]].set(
            jnp.where(validp[:, :, 1:], t_safe[:, :, :-1], -1).astype(_I32))
        ms = ms.at[w3, t_safe[:, :, :-1]].set(
            jnp.where(validp[:, :, 1:], t_safe[:, :, 1:], -1).astype(_I32))
        # trash slots may have been written with junk; cols >= n_b dropped,
        # but a t_safe of n_b inside the slice writes to col n_b only ✓
        return mp[:, :n_b], ms[:, :n_b]

    # ---------------------------------------------------------------- round
    def round_body(st):
        it = st["it"] + 1
        active0 = st["active"]
        start, finish = st["start"], st["finish"]
        seq, seq_len = st["seq"], st["seq_len"]
        assign, mem = st["assign"], st["mem"]
        mpred, msucc = st["mpred"], st["msucc"]
        cur_mk, best_mk = st["cur_mk"], st["best_mk"]

        # ---------------- critical set, then N7 and change-core moves ----- #
        with jax.named_scope("ts_move_gen"):
            dur_all = finish - start
            q_all = sdp.backward_q_xla(succ_mat, dur_all, msucc, n,
                                       active0[:, None])
            r_all = start
            slack = cur_mk[:, None] - r_all - q_all
            crit = (slack <= _EPS * jnp.maximum(1.0, cur_mk)[:, None]) \
                & (jnp.arange(n_b) < n)[None, :] & active0[:, None]
            crit_count = crit.sum(axis=1)
            overflow = (active0 & (crit_count > C)).any()

            # ---------------- move generation (N7) -------------------------- #
            col = jnp.arange(s_b)[None, None, :]
            validp = col < seq_len[:, :, None]
            seq_c = jnp.clip(seq, 0, n_b - 1)
            c_on = jnp.where(validp, _take_w(crit, seq_c.reshape(W, -1)
                                            ).reshape(W, p_b, s_b), False)
            prev = jnp.pad(c_on[:, :, :-1], ((0, 0), (0, 0), (1, 0)))
            nxt = jnp.pad(c_on[:, :, 1:], ((0, 0), (0, 0), (0, 1)))
            starts_m = c_on & ~prev
            ends_m = c_on & ~nxt
            sidx = jnp.broadcast_to(jnp.arange(s_b)[None, None, :], c_on.shape)
            lo_run = jax.lax.cummax(jnp.where(starts_m, sidx, -1), axis=2)
            hi_run = jax.lax.cummin(jnp.where(ends_m, sidx, s_b + 7), axis=2,
                                    reverse=True)
            keep = c_on & (hi_run - lo_run >= 1)
            flat_keep = keep.reshape(W, p_b * s_b)
            order_n7 = jnp.argsort(~flat_keep, axis=1, stable=True)[:, :C]
            slot_ok = jnp.take_along_axis(flat_keep, order_n7, axis=1)
            pp_n7 = (order_n7 // s_b).astype(_I32)
            ss_n7 = (order_n7 % s_b).astype(_I32)
            u_n7 = jnp.take_along_axis(seq_c.reshape(W, -1), order_n7, axis=1)
            lo_n7 = jnp.take_along_axis(lo_run.reshape(W, -1), order_n7, axis=1)
            hi_n7 = jnp.take_along_axis(hi_run.reshape(W, -1), order_n7, axis=1)
            # two moves per slot: [to-head, to-tail] interleaved
            n7_task = jnp.repeat(u_n7, 2, axis=1)
            n7_src_p = jnp.repeat(pp_n7, 2, axis=1)
            n7_src_s = jnp.repeat(ss_n7, 2, axis=1)
            n7_dst = jnp.stack([lo_n7, hi_n7], axis=2).reshape(W, M_n7)
            n7_valid = jnp.stack(
                [slot_ok & (ss_n7 != lo_n7), slot_ok & (ss_n7 != hi_n7)],
                axis=2).reshape(W, M_n7)

            # ---------------- move generation (change-core) ----------------- #
            crit_order = jnp.argsort(~crit, axis=1, stable=True)[:, :C]   # (W, C)
            crit_ok = jnp.take_along_axis(crit, crit_order, axis=1)
            u_cc = crit_order.astype(_I32)
            mach, pos = seq_positions(seq, seq_len)
            a_cc = _take_w(mach, u_cc)                                     # (W, C)
            k_cc = _take_w(pos, u_cc)
            r_starts = jnp.where(validp, _take_w(r_all, seq_c.reshape(W, -1)
                                                ).reshape(W, p_b, s_b), INF)
            r_u = _take_w(r_all, u_cc)                                     # (W, C)
            anchor = jax.vmap(jax.vmap(jnp.searchsorted, in_axes=(0, None)),
                              in_axes=(0, 0))(r_starts, r_u)              # (W, p_b, C)
            anchor = jnp.moveaxis(anchor, 1, 2)                           # (W, C, p_b)
            lo = jnp.maximum(0, anchor - NPOS // 2)
            hi = jnp.minimum(seq_len[:, None, :], lo + NPOS)
            jj = lo[..., None] + jnp.arange(NPOS + 1)[None, None, None, :]
            cc_valid = (jj <= hi[..., None]) \
                & crit_ok[:, :, None, None] \
                & compat[jnp.clip(u_cc, 0, n_b - 1)][..., None] \
                & (jnp.arange(p_b)[None, None, :, None] != a_cc[:, :, None, None]) \
                & (jnp.arange(p_b)[None, None, :, None] < p)
            cc_task = jnp.broadcast_to(u_cc[:, :, None, None], jj.shape)
            cc_src_p = jnp.broadcast_to(a_cc[:, :, None, None], jj.shape)
            cc_src_s = jnp.broadcast_to(k_cc[:, :, None, None], jj.shape)
            cc_dst_p = jnp.broadcast_to(
                jnp.arange(p_b, dtype=_I32)[None, None, :, None], jj.shape)

            mv_task = jnp.concatenate(
                [n7_task, cc_task.reshape(W, M_cc)], axis=1).astype(_I32)
            mv_src_p = jnp.concatenate(
                [n7_src_p, cc_src_p.reshape(W, M_cc)], axis=1).astype(_I32)
            mv_src_s = jnp.concatenate(
                [n7_src_s, cc_src_s.reshape(W, M_cc)], axis=1).astype(_I32)
            mv_dst_p = jnp.concatenate(
                [n7_src_p, cc_dst_p.reshape(W, M_cc)], axis=1).astype(_I32)
            mv_dst_s = jnp.concatenate(
                [n7_dst, jj.reshape(W, M_cc)], axis=1).astype(_I32)
            mv_cc = jnp.concatenate(
                [jnp.zeros((W, M_n7), bool), jnp.ones((W, M_cc), bool)], axis=1)
            mv_valid = jnp.concatenate(
                [n7_valid, cc_valid.reshape(W, M_cc)], axis=1) & active0[:, None]
            n_moves = mv_valid.sum(axis=1)
            participates = active0 & (n_moves > 0)
            n_approx = st["n_approx"] + jnp.where(active0, n_moves, 0).sum()

            # sanitize masked slots so downstream gathers stay in bounds
            mv_task = jnp.where(mv_valid, mv_task, 0)
            mv_src_p = jnp.where(mv_valid, mv_src_p, 0)
            mv_src_s = jnp.where(mv_valid, mv_src_s, 0)
            mv_dst_p = jnp.where(mv_valid, mv_dst_p, 0)
            mv_dst_s = jnp.where(mv_valid, mv_dst_s, 0)

        # ---------------- approximate evaluation ------------------------ #
        with jax.named_scope("ts_approx_eval"):
            est, finite = _window_estimates(
                ia, seq, seq_len, mem, dur_all, r_all, q_all,
                {"task": mv_task, "src_s": mv_src_s, "dst_p": mv_dst_p,
                 "dst_s": mv_dst_s, "cc": mv_cc, "valid": mv_valid})

            # ---------------- sort, tabu pre-filter ------------------------- #
            order = jnp.argsort(est, axis=1, stable=True)
            est_s = jnp.take_along_axis(est, order, axis=1)
            task_s = jnp.take_along_axis(mv_task, order, axis=1)
            srcp_s = jnp.take_along_axis(mv_src_p, order, axis=1)
            srcs_s = jnp.take_along_axis(mv_src_s, order, axis=1)
            dstp_s = jnp.take_along_axis(mv_dst_p, order, axis=1)
            dsts_s = jnp.take_along_axis(mv_dst_s, order, axis=1)
            cc_s = jnp.take_along_axis(mv_cc, order, axis=1)
            valid_s = jnp.take_along_axis(mv_valid & finite, order, axis=1)
            # resulting configuration (task, dst_proc, machine-pred-after-move)
            seq_dst_s = jnp.take_along_axis(seq, dstp_s[:, :, None], axis=1)
            pi = dsts_s - 1
            pio = pi + ((~cc_s) & (pi >= srcs_s))
            pred_cfg = jnp.where(
                pi >= 0,
                jnp.take_along_axis(seq_dst_s,
                                    jnp.clip(pio, 0, s_b - 1)[..., None],
                                    axis=2)[..., 0],
                -2)
            cfg_idx = (task_s.astype(jnp.int64) * p_b + dstp_s) * (n_b + 2) \
                + (pred_cfg + 2)
            expiry = jnp.take_along_axis(
                st["tabu"], jnp.clip(cfg_idx, 0, st["tabu"].shape[1] - 1), axis=1)
            is_tabu = expiry >= it
            adm = valid_s & ~(is_tabu & (est_s >= best_mk[:, None]))
            n_adm = adm.sum(axis=1)
            adm_perm = jnp.argsort(~adm, axis=1, stable=True)
            # compact admissible move attributes, in est order
            def comp(a):
                return jnp.take_along_axis(a, adm_perm, axis=1)
            c_task, c_srcp, c_srcs, c_dstp, c_dsts, c_cc, c_tabu = (
                comp(task_s), comp(srcp_s), comp(srcs_s), comp(dstp_s),
                comp(dsts_s), comp(cc_s), comp(is_tabu))

        # ---------------- chunked top-K exact evaluation ----------------- #
        with jax.named_scope("ts_exact_eval"):
            def apply_and_eval(sel_idx, slot_ok, *, arrs=None):
                """sel_idx (W, kk) indices into a move-array bundle — by default
                the compact admissible arrays (top-K chunks); the perturbation
                path passes the raw unsorted arrays instead and reuses this
                exact splice arithmetic at width 1."""
                task_a, srcs_a, dstp_a, dsts_a, cc_a = arrs if arrs is not None \
                    else (c_task, c_srcs, c_dstp, c_dsts, c_cc)
                kk = sel_idx.shape[1]
                u = jnp.take_along_axis(task_a, sel_idx, axis=1)
                ksrc = jnp.take_along_axis(srcs_a, sel_idx, axis=1)
                b = jnp.take_along_axis(dstp_a, sel_idx, axis=1)
                j = jnp.take_along_axis(dsts_a, sel_idx, axis=1)
                ccm = jnp.take_along_axis(cc_a, sel_idx, axis=1)
                u = jnp.where(slot_ok, u, 0)
                b = jnp.where(slot_ok, b, 0)
                x = _take_w(mpred, u)
                y = _take_w(msucc, u)
                w3 = jnp.broadcast_to(wi[:, None], (W, kk))
                k3 = jnp.broadcast_to(jnp.arange(kk)[None, :], (W, kk))
                mp = jnp.concatenate(
                    [jnp.broadcast_to(mpred[:, None, :], (W, kk, n_b)),
                     jnp.full((W, kk, 1), -1, _I32)], axis=2)
                ms = jnp.concatenate(
                    [jnp.broadcast_to(msucc[:, None, :], (W, kk, n_b)),
                     jnp.full((W, kk, 1), -1, _I32)], axis=2)
                asg = jnp.concatenate(
                    [jnp.broadcast_to(assign[:, None, :], (W, kk, n_b)),
                     jnp.zeros((W, kk, 1), _I32)], axis=2)

                def safe(t, okm):
                    return jnp.where(okm & slot_ok, t, n_b)

                ms = ms.at[w3, k3, safe(x, x >= 0)].set(y)
                mp = mp.at[w3, k3, safe(y, y >= 0)].set(x)
                dseq = jnp.take_along_axis(seq, b[:, :, None], axis=1)
                same = ~ccm
                len_dst = jnp.take_along_axis(seq_len, b, axis=1) - same
                pi2 = j - 1
                pio2 = pi2 + (same & (pi2 >= ksrc))
                pred_t = jnp.where(
                    pi2 >= 0,
                    jnp.take_along_axis(dseq, jnp.maximum(pio2, 0)[..., None],
                                        axis=2)[..., 0], -1)
                sio2 = j + (same & (j >= ksrc))
                succ_t = jnp.where(
                    j < len_dst,
                    jnp.take_along_axis(dseq,
                                        jnp.minimum(sio2, s_b - 1)[..., None],
                                        axis=2)[..., 0], -1)
                mp = mp.at[w3, k3, safe(u, slot_ok)].set(pred_t.astype(_I32))
                ms = ms.at[w3, k3, safe(u, slot_ok)].set(succ_t.astype(_I32))
                ms = ms.at[w3, k3, safe(pred_t, pred_t >= 0)].set(u)
                mp = mp.at[w3, k3, safe(succ_t, succ_t >= 0)].set(u)
                asg = asg.at[w3, k3, safe(u, slot_ok)].set(b)
                mem_rows = jnp.broadcast_to(
                    mem[:, None, :], (W, kk, d_b)).reshape(W * kk, d_b)
                start_c, finish_c, feas, mk = eval_candidates(
                    asg[:, :, :n_b].reshape(W * kk, n_b),
                    mp[:, :, :n_b].reshape(W * kk, n_b), mem_rows)
                return (start_c.reshape(W, kk, n_b), finish_c.reshape(W, kk, n_b),
                        feas.reshape(W, kk), mk.reshape(W, kk))

            def chunk_cond(cs):
                return cs["live"]

            def chunk_body(cs):
                pos, examined = cs["pos"], cs["examined"]
                done = cs["done"] \
                    | (cs["found"] & (examined >= K)) \
                    | (pos >= n_adm)
                avail = jnp.maximum(max_evals - cs["n_exact"], 0)
                want = jnp.where(participates & ~done,
                                 jnp.minimum(K, n_adm - pos), 0)
                # lint: allow[RPR103] DESIGN §9: exclusive prefix over small
                # nonneg ints is exact regardless of scan order; the §9 parity
                # hazard is float accumulation, which the blocked scan covers
                before = jnp.cumsum(want) - want
                size = jnp.clip(jnp.minimum(want, avail - before), 0, want)
                done = done | (want > 0) & (size <= 0)
                live = (size > 0).any()

                def do_eval(cs):
                    sel = pos[:, None] + jnp.arange(K)[None, :]
                    slot_ok = jnp.arange(K)[None, :] < size[:, None]
                    sel = jnp.where(slot_ok, jnp.clip(sel, 0, M - 1), 0)
                    start_c, finish_c, feas, mk = apply_and_eval(sel, slot_ok)
                    tabu_slot = jnp.take_along_axis(c_tabu, sel, axis=1)
                    elig = slot_ok & feas \
                        & ~(tabu_slot & (mk >= best_mk[:, None]))
                    mk_m = jnp.where(elig, mk, INF)
                    jmin = jnp.argmin(mk_m, axis=1)
                    cand_mk = jnp.take_along_axis(mk_m, jmin[:, None], axis=1)[:, 0]
                    better = cand_mk < cs["chosen_mk"]
                    sel_j = jnp.take_along_axis(sel, jmin[:, None], axis=1)[:, 0]
                    ch_start = jnp.take_along_axis(
                        start_c, jmin[:, None, None], axis=1)[:, 0]
                    ch_finish = jnp.take_along_axis(
                        finish_c, jmin[:, None, None], axis=1)[:, 0]
                    return {
                        "pos": pos + size,
                        "examined": examined + size,
                        "done": done,
                        "found": cs["found"] | better,
                        "chosen_i": jnp.where(better, sel_j, cs["chosen_i"]),
                        "chosen_mk": jnp.where(better, cand_mk, cs["chosen_mk"]),
                        "chosen_start": jnp.where(better[:, None], ch_start,
                                                  cs["chosen_start"]),
                        "chosen_finish": jnp.where(better[:, None], ch_finish,
                                                   cs["chosen_finish"]),
                        "n_exact": cs["n_exact"] + size.sum(),
                        "live": live,
                    }

                def no_eval(cs):
                    out = dict(cs)
                    out["done"] = done
                    out["live"] = live
                    return out

                return jax.lax.cond(live, do_eval, no_eval, cs)

            chunk0 = {
                "pos": jnp.zeros(W, jnp.int64),
                "examined": jnp.zeros(W, jnp.int64),
                "done": ~participates,
                "found": jnp.zeros(W, bool),
                "chosen_i": jnp.zeros(W, jnp.int64),
                "chosen_mk": jnp.full(W, INF),
                "chosen_start": jnp.zeros((W, n_b)),
                "chosen_finish": jnp.zeros((W, n_b)),
                "n_exact": st["n_exact"],
                "live": jnp.asarray(True),
            }
            cs = jax.lax.while_loop(chunk_cond, chunk_body, chunk0)
            n_exact = cs["n_exact"]
            found = cs["found"] & participates

        # ---------------- stalled walks: budget stop or perturbation ----- #
        with jax.named_scope("ts_perturb"):
            exhausted = participates & ~found & (n_exact >= max_evals)
            stop = st["stop"] | exhausted.any()
            perturb_w = participates & ~found & (n_exact < max_evals) \
                if cfg.perturb else jnp.zeros(W, bool)

            # perturbation: one threefry-random move per stalled walk, evaluated
            # as one extra (W, 1) candidate batch through the SAME splice/eval
            # path as the top-K chunks.  Everything (pick included) lives inside
            # the cond branch, so unstalled rounds — the overwhelming majority —
            # pay nothing for it.
            any_perturb = perturb_w.any()

            def perturb_eval(n_exact):
                fold = (wi.astype(jnp.uint32) * jnp.uint32(131071)
                        + it.astype(jnp.uint32))
                sub = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
                    jax.random.wrap_key_data(st["key"]), fold)
                valid_perm = jnp.argsort(~mv_valid, axis=1, stable=True)
                ridx = jax.vmap(
                    lambda kk, hi2: jax.random.randint(kk, (), 0, jnp.maximum(hi2, 1)))(
                    sub, n_moves)
                pick = jnp.take_along_axis(valid_perm, ridx[:, None], axis=1)
                slot_ok = perturb_w[:, None]
                start_c, finish_c, feas, mk = apply_and_eval(
                    pick, slot_ok,
                    arrs=(mv_task, mv_src_s, mv_dst_p, mv_dst_s, mv_cc))
                ok = perturb_w & feas[:, 0]

                def g(a):
                    return jnp.take_along_axis(a, pick, axis=1)[:, 0]

                return (ok, g(mv_task), g(mv_src_p), g(mv_src_s), g(mv_dst_p),
                        g(mv_dst_s), g(mv_cc), start_c[:, 0], finish_c[:, 0],
                        mk[:, 0], n_exact + jnp.where(perturb_w, 1, 0).sum())

            def perturb_skip(n_exact):
                z = jnp.zeros(W, _I32)
                return (jnp.zeros(W, bool), z, z, z, z, z,
                        jnp.zeros(W, bool), jnp.zeros((W, n_b)),
                        jnp.zeros((W, n_b)), jnp.full(W, INF), n_exact)

            (p_ok, p_u, p_a, p_k, p_b2, p_j, p_cc, p_start, p_finish, p_mk,
             n_exact) = jax.lax.cond(any_perturb, perturb_eval, perturb_skip,
                                     n_exact)

        # ---------------- commit (accepted move or feasible perturbation) #
        with jax.named_scope("ts_commit"):
            commit = found | p_ok
            cm_u = jnp.where(found, jnp.take_along_axis(
                c_task, cs["chosen_i"][:, None], axis=1)[:, 0], p_u).astype(_I32)
            cm_a = jnp.where(found, jnp.take_along_axis(
                c_srcp, cs["chosen_i"][:, None], axis=1)[:, 0], p_a).astype(_I32)
            cm_k = jnp.where(found, jnp.take_along_axis(
                c_srcs, cs["chosen_i"][:, None], axis=1)[:, 0], p_k).astype(_I32)
            cm_b = jnp.where(found, jnp.take_along_axis(
                c_dstp, cs["chosen_i"][:, None], axis=1)[:, 0], p_b2).astype(_I32)
            cm_j = jnp.where(found, jnp.take_along_axis(
                c_dsts, cs["chosen_i"][:, None], axis=1)[:, 0], p_j).astype(_I32)
            cm_cc = jnp.where(found, jnp.take_along_axis(
                c_cc, cs["chosen_i"][:, None], axis=1)[:, 0], p_cc)
            new_start = jnp.where(found[:, None], cs["chosen_start"],
                                  jnp.where(p_ok[:, None], p_start, start))
            new_finish = jnp.where(found[:, None], cs["chosen_finish"],
                                   jnp.where(p_ok[:, None], p_finish, finish))
            new_mk = jnp.where(found, cs["chosen_mk"],
                               jnp.where(p_ok, p_mk, cur_mk))

            # tabu the destroyed configuration (accepted moves only)
            mp_before = _take_w(mpred, cm_u[:, None])[:, 0]
            destroyed = (cm_u.astype(jnp.int64) * p_b + cm_a) * (n_b + 2) \
                + jnp.where(mp_before >= 0, mp_before, -2) + 2
            h_cc = _mix32_jnp(jnp, st["seed"], wi, it, jnp.uint32(1))
            h_n7 = _mix32_jnp(jnp, st["seed"], wi, it, jnp.uint32(0))
            tenure = jnp.where(
                cm_cc, p + h_cc.astype(jnp.int64) % (2 * p),
                n + h_n7.astype(jnp.int64) % jnp.maximum(n, 1))
            tabu_t = st["tabu"].at[
                wi, jnp.where(found, destroyed,
                              st["tabu"].shape[1])].set(
                jnp.where(found, (it + tenure).astype(_I32), 0),
                mode="drop")

            # sequence splice (dst row gets remove+insert arithmetic; cc moves
            # also rewrite the source row)
            ii = jnp.arange(s_b)[None, :]
            dst_row = jnp.take_along_axis(seq, cm_b[:, None, None], axis=1)[:, 0]
            new_len_b = jnp.take_along_axis(seq_len, cm_b[:, None], axis=1)[:, 0] \
                + cm_cc
            t2 = ii - (ii > cm_j[:, None])
            orig2 = t2 + ((~cm_cc)[:, None] & (t2 >= cm_k[:, None]))
            g2 = jnp.take_along_axis(dst_row, jnp.clip(orig2, 0, s_b - 1), axis=1)
            new_dst = jnp.where(ii == cm_j[:, None], cm_u[:, None], g2)
            new_dst = jnp.where(ii < new_len_b[:, None], new_dst, -1).astype(_I32)
            src_row = jnp.take_along_axis(seq, cm_a[:, None, None], axis=1)[:, 0]
            src_len = jnp.take_along_axis(seq_len, cm_a[:, None], axis=1)[:, 0]
            rem = jnp.take_along_axis(
                src_row, jnp.clip(ii + (ii >= cm_k[:, None]), 0, s_b - 1), axis=1)
            new_src = jnp.where(ii < (src_len - 1)[:, None], rem, -1).astype(_I32)
            parange = jnp.arange(p_b)[None, :, None]
            m_src = (parange == cm_a[:, None, None]) & (commit & cm_cc)[:, None, None]
            m_dst = (parange == cm_b[:, None, None]) & commit[:, None, None]
            seq_n = jnp.where(m_src, new_src[:, None, :], seq)
            seq_n = jnp.where(m_dst, new_dst[:, None, :], seq_n)
            parange2 = jnp.arange(p_b)[None, :]
            seq_len_n = seq_len \
                + ((parange2 == cm_b[:, None]) & commit[:, None]
                   & cm_cc[:, None]).astype(_I32) \
                - ((parange2 == cm_a[:, None]) & commit[:, None]
                   & cm_cc[:, None]).astype(_I32)
            assign_n = assign.at[
                wi, jnp.where(commit, cm_u, n_b)].set(cm_b, mode="drop")
            mp_n, ms_n = links_from_seq(seq_n, seq_len_n)

            start_n = jnp.where(commit[:, None], new_start, start)
            finish_n = jnp.where(commit[:, None], new_finish, finish)
            cur_mk_n = jnp.where(commit, new_mk, cur_mk)
            accepted_n = st["accepted"] + found.astype(_I32)

            improved = found & (cur_mk_n < best_mk - 1e-9)
            best_mk_n = jnp.where(improved, cur_mk_n, best_mk)
            unimp = jnp.where(
                improved, 0,
                st["unimproved"] + (participates & ~exhausted).astype(_I32))
            active_n = active0 & (n_moves > 0) & (unimp < max_unimp)

            st_out = dict(st)
            st_out.update(
                it=it, n_exact=n_exact, n_approx=n_approx, stop=stop,
                n_perturb=st["n_perturb"] + perturb_w.sum(),
                overflow=st["overflow"] | overflow,
                seq=seq_n, seq_len=seq_len_n, assign=assign_n,
                mpred=mp_n, msucc=ms_n,
                start=start_n, finish=finish_n, cur_mk=cur_mk_n,
                best_mk=best_mk_n, unimproved=unimp, accepted=accepted_n,
                active=active_n, tabu=tabu_t,
                best_seq=jnp.where(improved[:, None, None], seq_n, st["best_seq"]),
                best_seq_len=jnp.where(improved[:, None], seq_len_n,
                                       st["best_seq_len"]),
                best_assign=jnp.where(improved[:, None], assign_n,
                                      st["best_assign"]),
                best_mem=jnp.where(improved[:, None], mem, st["best_mem"]),
            )
        return st_out, overflow

    # ------------------------------------------------------------- run
    def run(st, series):
        def cond(carry):
            st, series, r = carry
            return (r < R) & st["active"].any() & ~st["stop"] \
                & ~st["overflow"] & (st["it"] < max_iters) \
                & (st["n_exact"] < max_evals)

        def body(carry):
            st, series, r = carry
            st2, overflow = round_body(st)

            def advance(_):
                s2 = dict(series)
                s2["best_mk"] = series["best_mk"].at[r].set(st2["best_mk"])
                s2["cur_mk"] = series["cur_mk"].at[r].set(st2["cur_mk"])
                s2["n_exact"] = series["n_exact"].at[r].set(st2["n_exact"])
                s2["it"] = series["it"].at[r].set(st2["it"])
                s2["active"] = series["active"].at[r].set(st2["active"])
                s2["ran"] = series["ran"].at[r].set(True)
                return st2, s2, r + 1

            return jax.lax.cond(overflow,
                                lambda _: (dict(st, overflow=jnp.asarray(True)),
                                           series, r + R),
                                advance, None)

        with jax.named_scope("ts_round"):
            st, series, _ = jax.lax.while_loop(
                cond, body, (st, series, jnp.int64(0)))
        return st, series

    return run


def _get_launch(ip: InstancePack, w_count: int, params: TSParams,
                crit_cap: int, cfg: DeviceConfig, *, batch: int = 0):
    """Fetch/compile the jitted launch for these buckets (bounded LRU).

    The instance arrays are always call ARGUMENTS, never baked-in jit
    constants: the cache key below describes only shapes and static search
    parameters, so two different instances that share buckets must be able
    to share one compiled program.  (``batch=I`` additionally vmaps over a
    leading instance axis of the arrays and the state.)"""
    import jax

    key = (ip.n_b, ip.p_b, ip.d_b, w_count, crit_cap, cfg.sync_every,
           params.top_k, params.n_change_core_positions,
           params.max_unimproved, params.max_iters, params.max_evals,
           cfg.perturb, cfg.donate,
           ip.pred_mat.shape[1], ip.succ_mat.shape[1],
           ip.in_blk.shape[1], ip.out_blk.shape[1],
           len(ip.in_idx), len(ip.out_idx), batch)
    fn = _LAUNCHES.get(key)
    if fn is not None:
        return fn, False

    def one(ia, st, series):
        return _round_loop(ia, w_count, params, crit_cap, cfg.sync_every,
                           cfg)(st, series)

    if batch:
        fn = jax.jit(jax.vmap(one, in_axes=(0, 0, 0)),
                     donate_argnums=(1,) if cfg.donate else ())
    else:
        fn = jax.jit(one, donate_argnums=(1,) if cfg.donate else ())
    _LAUNCHES.put(key, fn)
    # fresh=True: the first call will pay jit compilation — our own LRU is
    # the source of truth (no reliance on private jax attributes)
    return fn, True


def _series_buffers(rounds: int, w_count: int) -> dict:
    import jax.numpy as jnp

    return {
        "best_mk": jnp.zeros((rounds, w_count)),
        "cur_mk": jnp.zeros((rounds, w_count)),
        "n_exact": jnp.zeros(rounds, jnp.int64),
        "it": jnp.zeros(rounds, jnp.int64),
        "active": jnp.zeros((rounds, w_count), bool),
        "ran": jnp.zeros(rounds, bool),
    }


# --------------------------------------------------------------------------- #
# host driver                                                                  #
# --------------------------------------------------------------------------- #
def device_multiwalk(
    inst: Instance,
    inits: list[Solution],
    params: TSParams | None = None,
    *,
    config: DeviceConfig | None = None,
    init_labels: list[str] | None = None,
    on_iteration=None,
    on_improvement=None,
    on_checkpoint=None,
    resume_from=None,
) -> MultiWalkResult:
    """Drop-in ``tabu_multiwalk`` with the round loop on-device.

    Callbacks fire at sync boundaries (every ``config.sync_every`` rounds)
    rather than per iteration; Algorithm 3 runs at the same boundaries when
    ``params.mem_update_period < MEM_UPDATE_DISABLED``.

    ``on_checkpoint`` (optional) receives a
    :class:`~repro.faults.checkpoint.SearchCheckpoint` at every sync
    boundary (after Alg-3, before the next launch) — the full walk state
    plus host trajectory.  ``resume_from`` restarts the run from such a
    checkpoint **bit-identically**: every remaining launch sees exactly the
    state the uncrashed run would have, so under iteration/eval budgets the
    final result matches field-for-field (wall-clock fields excepted; a
    ``time_limit`` budget carries the checkpoint's elapsed over instead of
    restarting).  Both are None-default and cost nothing when unused
    (DESIGN.md §13).
    """
    from jax import enable_x64

    params = params or TSParams()
    cfg = config or DeviceConfig()
    w_count = len(inits) if resume_from is None else int(resume_from.walks)
    if w_count < 1:
        raise ValueError("device_multiwalk needs at least one init")
    labels = init_labels or [f"walk{w}" for w in range(w_count)]
    t0 = time.monotonic()

    ckpt_fp = None
    if on_checkpoint is not None or resume_from is not None:
        from ..faults import checkpoint as _ckpt

        ckpt_fp = (_ckpt.instance_fingerprint(inst),
                   _ckpt.params_fingerprint(params))
    from ..faults import inject as _inject

    ip = pack_instance(inst)
    if resume_from is not None:
        _ckpt.check_compatible(resume_from, instance_fp=ckpt_fp[0],
                               params_fp=ckpt_fp[1], walks=w_count)
        state = {k: np.array(v) for k, v in resume_from.state.items()}
        feasible = {k: np.array(v) for k, v in resume_from.feasible.items()}
        crit_cap = int(resume_from.crit_cap)
        histories = [list(h) for h in resume_from.histories]
        g_best = float(resume_from.g_best)
        g_hist = list(resume_from.g_hist)
        init_mk_min = float(resume_from.init_mk_min)
        n_exact_host = int(resume_from.n_exact_host)
        sync_index = int(resume_from.sync_index)
        t0 -= float(resume_from.elapsed)  # time budget carries over
    else:
        cur_sols = [_alg3(inst, init, params, {}) for init in inits]
        scheds = [exact_schedule(inst, s) for s in cur_sols]
        if not all(s is not None for s in scheds):
            raise ValueError("initial solutions must be acyclic")

        state = pack_state(ip, cur_sols, scheds, params.seed)
        feasible = _feasible_record(state)
        crit_cap = cfg.crit_cap or _auto_crit_cap(inst, cur_sols, scheds)

        best_mk0 = state["best_mk"].copy()
        histories = [[(0, float(best_mk0[w]))] for w in range(w_count)]
        g_best = float(best_mk0.min())
        g_hist = [(0, g_best)]
        init_mk_min = g_best
        n_exact_host = 0  # host-side Alg-3 re-evals (mirrors legacy +1)
        sync_index = 0
    mem_updates_on = params.mem_update_period < MEM_UPDATE_DISABLED
    stop_reason = "converged"
    compile_s = 0.0

    def _snapshot():
        return _ckpt.snapshot(
            instance_fp=ckpt_fp[0], params_fp=ckpt_fp[1], walks=w_count,
            sync_index=sync_index, crit_cap=crit_cap,
            elapsed=time.monotonic() - t0, n_exact_host=n_exact_host,
            g_best=g_best, init_mk_min=init_mk_min, g_hist=g_hist,
            histories=histories, state=state, feasible=feasible)

    def _fire(cb, improved: bool, it: int, cur_min: float) -> bool:
        if cb is None:
            return False
        ev = TSEvent(iteration=it, best_makespan=g_best,
                     current_makespan=cur_min,
                     elapsed=time.monotonic() - t0,
                     n_exact_evals=int(state["n_exact"]) + n_exact_host,
                     n_approx_evals=int(state["n_approx"]),
                     improved=improved)
        return bool(cb(ev))

    with enable_x64():
        import jax.numpy as jnp

        ia_j = {k2: jnp.asarray(v) for k2, v in ia_from_pack(ip).items()}
        while True:
            if time.monotonic() - t0 > params.time_limit:
                stop_reason = "time_limit"
                break
            tc = time.monotonic()
            launch, fresh = _get_launch(ip, w_count, params, crit_cap, cfg)
            state_j = {k2: jnp.asarray(v) for k2, v in state.items()}
            state_j, series = launch(ia_j, state_j,
                                     _series_buffers(cfg.sync_every, w_count))
            if fresh:
                # first call on these buckets pays jit compilation; the
                # benches report it separately from steady-state throughput
                compile_s += time.monotonic() - tc
            state = {k2: np.array(v) for k2, v in state_j.items()}  # writable
            ser = {k2: np.asarray(v) for k2, v in series.items()}

            g_improved = False
            for r in range(cfg.sync_every):
                if not ser["ran"][r]:
                    break
                it_r = int(ser["it"][r])
                for w in range(w_count):
                    bmk = float(ser["best_mk"][r, w])
                    if bmk < histories[w][-1][1] - 1e-9:
                        histories[w].append((it_r, bmk))
                nb = float(ser["best_mk"][r].min())
                if nb < g_best:
                    g_best = nb
                    g_hist.append((it_r, g_best))
                    g_improved = True

            if state["overflow"]:
                state["overflow"] = np.bool_(False)
                crit_cap = max(crit_cap * 2, 32)
                if crit_cap > ip.n_b:
                    crit_cap = ip.n_b
                _note_overflow_relaunch()
                continue

            it_now = int(state["it"])
            cur_min = float(state["cur_mk"][state["active"]].min()) \
                if state["active"].any() else g_best
            if g_improved and _fire(on_improvement, True, it_now, cur_min):
                stop_reason = "callback"
                break
            if _fire(on_iteration, g_improved, it_now, cur_min):
                stop_reason = "callback"
                break

            if not state["active"].any():
                stop_reason = "converged"
                break
            if params.max_iters is not None and it_now >= params.max_iters:
                stop_reason = "max_iters"
                break
            if params.max_evals is not None and \
                    int(state["n_exact"]) >= params.max_evals:
                stop_reason = "max_evals"
                break
            if state["stop"]:
                stop_reason = "max_evals"
                break

            if mem_updates_on:
                for w in range(w_count):
                    if not state["active"][w]:
                        continue
                    sol_w = unpack_solution(ip, state["seq"], state["seq_len"],
                                            state["assign"], state["mem"], w)
                    sol_w = _alg3(inst, sol_w, params, {})
                    sched_w = exact_schedule(inst, sol_w)
                    if sched_w is None:
                        raise RuntimeError("memory_update returned a cyclic solution")
                    n_exact_host += 1
                    _write_walk(ip, state, w, sol_w, sched_w)
                    _note_feasible(feasible, state, w)
                    if sched_w.makespan < state["best_mk"][w] - 1e-9:
                        state["best_mk"][w] = sched_w.makespan
                        state["best_seq"][w] = state["seq"][w]
                        state["best_seq_len"][w] = state["seq_len"][w]
                        state["best_assign"][w] = state["assign"][w]
                        state["best_mem"][w] = state["mem"][w]
                        histories[w].append((it_now, float(sched_w.makespan)))
                        _maybe_sanitize(
                            inst, sol_w,
                            f"device_multiwalk sync incumbent walk {w}",
                            params, mk=float(sched_w.makespan))
                        if sched_w.makespan < g_best:
                            g_best = float(sched_w.makespan)
                            g_hist.append((it_now, g_best))

            sync_index += 1
            if on_checkpoint is not None:
                on_checkpoint(_snapshot())
            # chaos harness: a seeded plan can lose the device at a sync
            # boundary — after the checkpoint, so the crash is survivable
            _inject.fire("device_search.sync", key=sync_index)

    best_sols = [
        unpack_solution(ip, state["best_seq"], state["best_seq_len"],
                        state["best_assign"], state["best_mem"], w)
        for w in range(w_count)
    ]
    best_mk = np.array(state["best_mk"])
    if mem_updates_on:
        # in-launch incumbents were taken with a frozen allocation; the
        # report upholds the legacy drivers' feasibility contract
        best_sols, best_mk = _repair_bests(inst, params, ip, best_sols,
                                           best_mk, feasible, {})
    gi = int(np.argmin(best_mk))
    _maybe_sanitize(inst, best_sols[gi], "device_multiwalk final best",
                    params, mk=float(best_mk[gi]), capacity=mem_updates_on)
    per_walk = [
        WalkInfo(init_label=labels[w], initial_makespan=histories[w][0][1],
                 best_makespan=float(best_mk[w]), best=best_sols[w],
                 history=histories[w],
                 stop_reason=stop_reason if state["active"][w] else "converged")
        for w in range(w_count)
    ]
    res = MultiWalkResult(
        best=best_sols[gi],
        best_makespan=float(best_mk[gi]),
        initial_makespan=init_mk_min,
        iterations=int(state["it"]),
        elapsed=time.monotonic() - t0,
        history=g_hist,
        n_exact_evals=int(state["n_exact"]) + n_exact_host,
        n_approx_evals=int(state["n_approx"]),
        stop_reason=stop_reason,
        n_perturbations=int(state["n_perturb"]),
        walks=w_count,
        per_walk=per_walk,
    )
    res.compile_seconds = compile_s  # type: ignore[attr-defined]
    return res


def _alg3(inst: Instance, sol: Solution, params: TSParams, span: dict) -> Solution:
    """Algorithm 3 at the search's settings, recorded as the host span
    ``repro.search.alg3`` (``span``: its metadata, the cut's id)."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("repro.search.alg3", **span):
        return memory_update(inst, sol, refresh_every=params.mem_refresh_every,
                             scalar=params.mem_update_scalar)


_FEASIBLE_ROWS = ("seq", "seq_len", "assign", "mem")


def _feasible_record(state: dict) -> dict:
    """Each walk's best capacity-feasible schedule seen on the host: copies
    of its packed rows and its exact makespan (``mk``), seeded from the
    walks of ``state`` (their starts after Algorithm 3)."""
    rec = {k: np.array(state[k]) for k in _FEASIBLE_ROWS}
    rec["mk"] = np.array(state["cur_mk"], dtype=np.float64)
    return rec


def _note_feasible(rec: dict, state: dict, w: int) -> None:
    """Keep walk ``w``'s rows of ``state``, just written after Algorithm 3
    with their exact makespan in ``cur_mk``, where they beat its record."""
    if state["cur_mk"][w] < rec["mk"][w]:
        for k in _FEASIBLE_ROWS:
            rec[k][w] = state[k][w]
        rec["mk"][w] = state["cur_mk"][w]


def _repair_bests(inst: Instance, params: TSParams, ip: InstancePack,
                  best_sols, best_mk, feasible: dict, span: dict):
    """Serve every walk a capacity-feasible best.  A device best was taken
    under the allocation frozen since the last sync and may be over
    capacity: Algorithm 3 re-runs on it, and where the repaired schedule
    comes out worse than the walk's best feasible one (``feasible``, from
    :func:`_feasible_record`), that one is served.  Ties go to the repair.
    Counted in ``REPAIRS``."""
    from .solution import memory_feasible

    tally = collections.Counter(walks=len(best_sols))
    for w, sol in enumerate(best_sols):
        sched = exact_schedule(inst, sol)
        assert sched is not None
        if memory_feasible(inst, sol, sched):
            continue
        tally["infeasible"] += 1
        sol = _alg3(inst, sol, params, span)
        sched = exact_schedule(inst, sol)
        assert sched is not None
        best_mk[w] = sched.makespan
        if sched.makespan > feasible["mk"][w]:
            tally["fallback"] += 1
            sol = unpack_solution(ip, feasible["seq"], feasible["seq_len"],
                                  feasible["assign"], feasible["mem"], w)
            best_mk[w] = feasible["mk"][w]
        best_sols[w] = sol
    with _REPAIRS_LOCK:
        REPAIRS.update(tally)
    return best_sols, best_mk


def _auto_crit_cap(inst, sols, scheds) -> int:
    from ..kernels import schedule_dp as sdp
    from .solution import heads_tails

    worst = 16
    for sol, sched in zip(sols, scheds):
        _, _, _, crit = heads_tails(inst, sol, sched)
        worst = max(worst, int(crit.sum()))
    # no headroom factor: overflow escalation doubles the bucket on demand,
    # and a tight capacity halves the padded neighborhood the window kernel
    # and sorts chew through every round
    return min(sdp.bucket(worst, 32), inst.n_tasks)


def _write_walk(ip: InstancePack, state: dict, w: int, sol: Solution,
                sched) -> None:
    """Host-side overwrite of one walk's packed rows (after Alg-3)."""
    state["seq"][w] = -1
    state["seq_len"][w] = 0
    state["mpred"][w] = -1
    state["msucc"][w] = -1
    _fill_seq_rows(sol, state["seq"][w], state["seq_len"][w],
                   state["mpred"][w], state["msucc"][w])
    state["assign"][w, : ip.n] = sol.assign
    state["mem"][w, : ip.d] = sol.mem
    state["start"][w] = 0.0
    state["finish"][w] = 0.0
    state["start"][w, : ip.n] = sched.start
    state["finish"][w, : ip.n] = sched.finish
    state["cur_mk"][w] = sched.makespan


# --------------------------------------------------------------------------- #
# instance-vmapped sweeps                                                      #
# --------------------------------------------------------------------------- #
def solve_instances(
    instances: "list[Instance] | InstanceBatch",
    inits: list[list[Solution]],
    params: TSParams | None = None,
    *,
    config: DeviceConfig | None = None,
    seeds: "list[int] | None" = None,
    callbacks: "list | None" = None,
    cut: "int | None" = None,
) -> list[MultiWalkResult]:
    """Run the device engine over a batch of same-bucket instances in one
    vmapped compiled call per sync — an entire Table-II row per launch.

    ``instances`` may be a plain list (converted here) or a prebuilt
    :class:`~repro.instances.InstanceBatch` — the packed/bucketed boundary
    object the suite sweep constructs once per bucket group.  All instances
    are padded to shared shape buckets and their real sizes ride along as
    traced scalars; every loop update is masked, and JAX's ``while_loop``
    batching keeps finished instances' state frozen, so per-instance
    results are identical to per-instance ``device_multiwalk`` calls with
    the same ``crit_cap`` (asserted by ``tests/test_device_search.py``).
    Budgets apply per instance; wall time is checked between launches.
    Algorithm 3 runs host-side at sync boundaries exactly like the
    single-instance driver.

    ``seeds`` gives each instance its own search seed (tenure/perturbation
    stream — the value ``params.seed`` carries on a solo run); the compiled
    launch is seed-independent, so mixed-seed batches still share one
    program.  ``callbacks`` is an optional per-instance list of
    :class:`~repro.core.api.Callbacks`-shaped objects (``None`` entries
    allowed): ``on_improvement``/``on_iteration`` fire per instance at sync
    boundaries with that instance's own :class:`TSEvent`, and a truthy
    return stops *that instance only* (its ``stop_reason`` becomes
    ``"callback"``).  This is the anytime-incumbent path the serve engine
    fans out to streaming clients.

    The host work is recorded as profiler spans (``repro.search.prep``,
    then per launch ``launch``, ``readback`` and ``sync``, and ``finish``,
    with ``repro.search.alg3`` around each Algorithm 3 inside them), each
    carrying ``cut`` as metadata where it is given: the serve engine passes
    its cut's head request id.
    """
    import jax
    import jax.numpy as jnp
    from jax import enable_x64
    from jax.profiler import TraceAnnotation

    params = params or TSParams()
    cfg = config or DeviceConfig()
    batch = instances if isinstance(instances, InstanceBatch) \
        else InstanceBatch.from_instances(instances)
    instances = list(batch.instances)
    n_inst = len(instances)
    if n_inst < 1 or len(inits) != n_inst:
        raise ValueError("need at least one instance and one init list per instance")
    w_count = len(inits[0])
    if not all(len(x) == w_count for x in inits):
        raise ValueError("equal walk counts required")
    if seeds is None:
        seeds = [params.seed] * n_inst
    if len(seeds) != n_inst:
        raise ValueError("one seed per instance")
    if callbacks is not None and len(callbacks) != n_inst:
        raise ValueError("one callback slot per instance")
    t0 = time.monotonic()

    span = {} if cut is None else {"cut": cut}
    with TraceAnnotation("repro.search.prep", **span):
        cur_sols, scheds = [], []
        for inst, init_list in zip(instances, inits):
            sols = [_alg3(inst, s, params, span) for s in init_list]
            sc = [exact_schedule(inst, s) for s in sols]
            if not all(x is not None for x in sc):
                raise ValueError("initial solutions must be acyclic")
            cur_sols.append(sols)
            scheds.append(sc)

        # shared buckets live on the batch: every padded axis is the max bucket
        # across the batch, computed once at InstanceBatch construction
        n_b = batch.n_b
        packs = list(batch.packs)
        crit_cap = cfg.crit_cap or max(
            _auto_crit_cap(i, s, sc)
            for i, s, sc in zip(instances, cur_sols, scheds))

        states = [pack_state(ip2, s, sc, sd)
                  for ip2, s, sc, sd in zip(packs, cur_sols, scheds, seeds)]
        init_best = np.stack([st["best_mk"] for st in states])   # (I, W)
        histories = [[[(0, float(init_best[i, w]))] for w in range(w_count)]
                     for i in range(n_inst)]
        g_hist = [[(0, float(init_best[i].min()))] for i in range(n_inst)]
        g_best = [h[0][1] for h in g_hist]
        mem_updates_on = params.mem_update_period < MEM_UPDATE_DISABLED
        n_exact_host = np.zeros(n_inst, dtype=np.int64)
        cb_stop = np.zeros(n_inst, dtype=bool)
        timed_out = False
        compile_s = 0.0

        state = {k: np.stack([st[k] for st in states]) for k in states[0]}
        feasible = _feasible_record(state)   # (I, W, ...)
        with enable_x64():
            ia_j = {k: jnp.asarray(v) for k, v in batch.arrays().items()}

    with enable_x64():
        while True:
            if time.monotonic() - t0 > params.time_limit:
                timed_out = True
                break
            with TraceAnnotation("repro.search.launch", **span):
                tc = time.monotonic()
                launch, fresh = _get_launch(packs[0], w_count, params, crit_cap,
                                            cfg, batch=n_inst)
                state_j = {k: jnp.asarray(v) for k, v in state.items()}
                series0 = jax.vmap(
                    lambda _: _series_buffers(cfg.sync_every, w_count))(
                    jnp.arange(n_inst))
                state_j, series = launch(ia_j, state_j, series0)
                if fresh:
                    compile_s += time.monotonic() - tc
            with TraceAnnotation("repro.search.readback", **span):
                state = {k: np.array(v) for k, v in state_j.items()}  # writable
                ser = {k: np.asarray(v) for k, v in series.items()}

            with TraceAnnotation("repro.search.sync", **span):
                sync_improved = np.zeros(n_inst, dtype=bool)
                for i in range(n_inst):
                    for r in range(cfg.sync_every):
                        if not ser["ran"][i, r]:
                            continue
                        it_r = int(ser["it"][i, r])
                        for w in range(w_count):
                            bmk = float(ser["best_mk"][i, r, w])
                            if bmk < histories[i][w][-1][1] - 1e-9:
                                histories[i][w].append((it_r, bmk))
                        nb = float(ser["best_mk"][i, r].min())
                        if nb < g_best[i]:
                            g_best[i] = nb
                            g_hist[i].append((it_r, nb))
                            sync_improved[i] = True

                if state["overflow"].any():
                    state["overflow"][:] = False
                    crit_cap = min(max(crit_cap * 2, 32), n_b)
                    _note_overflow_relaunch()
                    continue

                if callbacks is not None:
                    # per-instance anytime hooks, fired at the same boundary the
                    # single-instance driver uses (after overflow handling, before
                    # Alg-3); a truthy return retires only that instance
                    for i in range(n_inst):
                        cb = callbacks[i]
                        if cb is None or cb_stop[i]:
                            continue
                        act = state["active"][i]
                        if not act.any() and not sync_improved[i]:
                            continue
                        cur_min = float(state["cur_mk"][i][act].min()) \
                            if act.any() else g_best[i]
                        ev = TSEvent(
                            iteration=int(state["it"][i]),
                            best_makespan=g_best[i],
                            current_makespan=cur_min,
                            elapsed=time.monotonic() - t0,
                            n_exact_evals=int(state["n_exact"][i])
                            + int(n_exact_host[i]),
                            n_approx_evals=int(state["n_approx"][i]),
                            improved=bool(sync_improved[i]))
                        on_imp = getattr(cb, "on_improvement", None)
                        if sync_improved[i] and on_imp is not None and on_imp(ev):
                            cb_stop[i] = True
                        on_it = getattr(cb, "on_iteration", None)
                        if not cb_stop[i] and on_it is not None and on_it(ev):
                            cb_stop[i] = True
                        if cb_stop[i]:
                            state["active"][i, :] = False

                done = ~state["active"].any(axis=1) | state["stop"]
                if params.max_iters is not None:
                    done |= state["it"] >= params.max_iters
                if params.max_evals is not None:
                    done |= state["n_exact"] >= params.max_evals
                if done.all():
                    break

                if mem_updates_on:
                    for i in range(n_inst):
                        if done[i]:
                            continue
                        sub = {k: state[k][i] for k in state}
                        feas = {k: v[i] for k, v in feasible.items()}  # views
                        for w in range(w_count):
                            if not sub["active"][w]:
                                continue
                            sol_w = unpack_solution(packs[i], sub["seq"],
                                                    sub["seq_len"], sub["assign"],
                                                    sub["mem"], w)
                            sol_w = _alg3(instances[i], sol_w, params, span)
                            sched_w = exact_schedule(instances[i], sol_w)
                            if sched_w is None:
                                raise RuntimeError(
                                    "memory_update returned a cyclic solution")
                            n_exact_host[i] += 1
                            _write_walk(packs[i], sub, w, sol_w, sched_w)
                            _note_feasible(feas, sub, w)
                            if sched_w.makespan < sub["best_mk"][w] - 1e-9:
                                sub["best_mk"][w] = sched_w.makespan
                                sub["best_seq"][w] = sub["seq"][w]
                                sub["best_seq_len"][w] = sub["seq_len"][w]
                                sub["best_assign"][w] = sub["assign"][w]
                                sub["best_mem"][w] = sub["mem"][w]
                                it_now = int(sub["it"])
                                histories[i][w].append(
                                    (it_now, float(sched_w.makespan)))
                                if sched_w.makespan < g_best[i]:
                                    g_best[i] = float(sched_w.makespan)
                                    g_hist[i].append((it_now, g_best[i]))
                        for k in state:
                            state[k][i] = sub[k]

    results = []
    with TraceAnnotation("repro.search.finish", **span):
        for i in range(n_inst):
            active = state["active"][i]
            if cb_stop[i]:
                stop_reason = "callback"
            elif not active.any():
                stop_reason = "converged"
            elif timed_out:
                stop_reason = "time_limit"
            elif params.max_iters is not None and \
                    state["it"][i] >= params.max_iters:
                stop_reason = "max_iters"
            elif state["stop"][i] or (params.max_evals is not None and
                                      state["n_exact"][i] >= params.max_evals):
                stop_reason = "max_evals"
            else:
                stop_reason = "time_limit"
            best_mk = np.array(state["best_mk"][i])
            best_sols = [
                unpack_solution(packs[i], state["best_seq"][i],
                                state["best_seq_len"][i], state["best_assign"][i],
                                state["best_mem"][i], w)
                for w in range(w_count)
            ]
            if mem_updates_on:
                best_sols, best_mk = _repair_bests(
                    instances[i], params, packs[i], best_sols, best_mk,
                    {k: v[i] for k, v in feasible.items()}, span)
            gi = int(np.argmin(best_mk))
            _maybe_sanitize(instances[i], best_sols[gi],
                            f"solve_instances final best (instance {i})",
                            params, mk=float(best_mk[gi]),
                            capacity=mem_updates_on)
            per_walk = [
                WalkInfo(init_label=f"walk{w}",
                         initial_makespan=histories[i][w][0][1],
                         best_makespan=float(best_mk[w]), best=best_sols[w],
                         history=histories[i][w],
                         stop_reason=stop_reason if active[w] else "converged")
                for w in range(w_count)
            ]
            res = MultiWalkResult(
                best=best_sols[gi], best_makespan=float(best_mk[gi]),
                initial_makespan=float(init_best[i].min()),
                iterations=int(state["it"][i]),
                elapsed=time.monotonic() - t0,
                history=g_hist[i],
                n_exact_evals=int(state["n_exact"][i]) + int(n_exact_host[i]),
                n_approx_evals=int(state["n_approx"][i]),
                stop_reason=stop_reason, walks=w_count, per_walk=per_walk,
            )
            res.compile_seconds = compile_s  # type: ignore[attr-defined]
            results.append(res)
    return results


# --------------------------------------------------------------------------- #
# warm pool                                                                    #
# --------------------------------------------------------------------------- #
def warm_launches(
    instances: "list[Instance] | InstanceBatch",
    walks: int,
    params: TSParams | None = None,
    *,
    config: DeviceConfig | None = None,
    batch_sizes: tuple = (1,),
) -> dict:
    """Pre-compile the ``solve_instances`` programs one launch shape needs.

    ``instances`` (a list or prebuilt :class:`InstanceBatch`) declares the
    shape — shared buckets, dense widths, padded edge lengths; ``walks`` and
    ``params`` supply the compile-relevant search knobs; ``batch_sizes`` are
    the vmap widths to warm (the serve engine's quantized batch sizes).
    Each missing program is compiled by invoking it once on a replicated
    copy of the first instance with every walk inactive, so the round loop
    exits at once and the timed call is the compile, not a search horizon;
    the executable lands in both the in-process launch LRU and — when
    ``jax_compilation_cache_dir`` is set — JAX's persistent compilation
    cache.  Returns per-size compile seconds and launch-cache counter
    deltas.
    """
    import jax
    from jax import enable_x64

    from .api import multiwalk_inits  # lazy: api imports this module lazily

    params = params or TSParams()
    cfg = config or DeviceConfig()
    batch = instances if isinstance(instances, InstanceBatch) \
        else InstanceBatch.from_instances(instances)
    inst = batch.instances[0]
    ip = batch.packs[0]
    cap = cfg.crit_cap or batch.n_b
    init_sols, _ = multiwalk_inits(inst, walks, params.seed)
    sols = [memory_update(inst, s, refresh_every=params.mem_refresh_every,
                          scalar=params.mem_update_scalar) for s in init_sols]
    scheds = [exact_schedule(inst, s) for s in sols]
    if not all(s is not None for s in scheds):
        raise ValueError("warm instance must be solvable")
    before = launch_cache_info()
    per_size: dict = {}
    with enable_x64():
        import jax.numpy as jnp

        ia = ia_from_pack(ip)
        state = pack_state(ip, sols, scheds, params.seed)
        state["active"][:] = False  # compile only: zero rounds run
        for bs in sorted({int(b) for b in batch_sizes}):
            if bs < 1:
                raise ValueError("batch sizes must be positive")
            t0 = time.monotonic()
            launch, fresh = _get_launch(ip, walks, params, cap, cfg, batch=bs)
            if fresh:
                ia_b = {k: jnp.asarray(np.stack([v] * bs))
                        for k, v in ia.items()}
                st_b = {k: jnp.asarray(np.stack([v] * bs))
                        for k, v in state.items()}
                series0 = jax.vmap(
                    lambda _: _series_buffers(cfg.sync_every, walks))(
                    jnp.arange(bs))
                out_state, _series = launch(ia_b, st_b, series0)
                jax.block_until_ready(out_state)
            per_size[bs] = {"fresh": fresh,
                            "seconds": time.monotonic() - t0}
    after = launch_cache_info()
    return {
        "bucket_key": batch.bucket_key,
        "per_size": per_size,
        "compile_seconds": sum(v["seconds"] for v in per_size.values()
                               if v["fresh"]),
        "cache_delta": {k: after[k] - before[k]
                        for k in ("hits", "misses", "evictions",
                                  "overflow_relaunches")},
        "cache": after,
    }
