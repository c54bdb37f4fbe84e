"""The device round's approximate evaluation, held to the bit.

The window estimate reads, per task, its predecessors' durations and old
finishes, and per (task, core) the re-priced move-in and move-out times.
The round builds those once as tables and reads them per move as rows.
Three checks hold it to the per-move formulation it replaced:

* whole answers of ``solve_instances`` against answers recorded with the
  per-move formulation (``fixtures/approx_eval_golden.json``), with
  Algorithm 3 on, at the benchmark's recipes at rehearsal size: roomy and
  20%-tight fast memory, the FFT graph, and fractional block sizes;
* the estimate vector against a copy of the per-move formulation kept here
  as the oracle (``per_move_estimates``), on random moves over packed
  walk states;
* the lowered round program: no gather under ``ts_approx_eval`` takes one
  scalar per (walk, move, predecessor slot).
"""
import dataclasses
import hashlib
import json
import pathlib
import re

import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import TSParams  # noqa: E402
from repro.core.api import multiwalk_inits  # noqa: E402
from repro.core.device_search import (  # noqa: E402
    DeviceConfig,
    _get_launch,
    _series_buffers,
    _window_estimates,
    pack_state,
    solve_instances,
)
from repro.core.eval_batch import APPROX_WINDOW, _new_seq_at  # noqa: E402
from repro.core.solution import exact_schedule  # noqa: E402
from repro.instances.batch import InstanceBatch, ia_from_pack, pack_instance  # noqa: E402
from repro.instances.suites import load_npz  # noqa: E402

FIXTURES = pathlib.Path(__file__).parent / "fixtures"
INSTANCES = {i.name: i for path in ("feasible_best_instances.npz",
                                    "greedy_golden_instances.npz")
             for i in load_npz(str(FIXTURES / path))}
WALKS = 4
# four instances per launch, as the serve engine batches them
BATCHES = {
    "roomy": (["roomy40-16-0", "roomy40-16-1", "roomy40-16-2", "roomy40-16-3"], 3),
    "tight": (["layered40-16-4", "layered40-16-5", "layered40-16-6",
               "layered40-16-7"], 3),
    "fft": (["fft8-16-0", "fft8-16-1", "fft8-16-2", "fft8-16-3"], 2),
    "fractional": (["tight40_nonint", "tight40", "roomy40", "fft8x3"], 3),
}


def schedule_hash(sol) -> str:
    rec = {"assign": [int(x) for x in sol.assign], "mem": [int(x) for x in sol.mem],
           "proc_seq": [[int(t) for t in s] for s in sol.proc_seq]}
    return hashlib.sha256(json.dumps(rec).encode()).hexdigest()[:16]


def answers(batch: str) -> list:
    """Each instance's answer as the serve engine runs it: one sync per round,
    the critical set at full capacity, Algorithm 3 at every sync."""
    names, iters = BATCHES[batch]
    batch = InstanceBatch.from_instances([INSTANCES[n] for n in names])
    seeds = list(range(len(names)))
    inits = [multiwalk_inits(inst, WALKS, s)[0]
             for s, inst in zip(seeds, batch.instances)]
    results = solve_instances(
        batch, inits, dataclasses.replace(TSParams(), max_iters=iters),
        config=DeviceConfig(sync_every=1, crit_cap=batch.n_b), seeds=seeds)
    return [{
        "instance": name,
        "best_makespan": res.best_makespan,
        "initial_makespan": res.initial_makespan,
        "best": schedule_hash(res.best),
        "iterations": res.iterations,
        "n_exact_evals": res.n_exact_evals,
        "n_approx_evals": res.n_approx_evals,
        "n_perturbations": res.n_perturbations,
        "history": [list(h) for h in res.history],
        "walks": [{"initial_makespan": w.initial_makespan,
                   "best_makespan": w.best_makespan,
                   "best": schedule_hash(w.best),
                   "history": [list(h) for h in w.history],
                   "stop_reason": w.stop_reason} for w in res.per_walk],
    } for name, res in zip(names, results)]


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_answers_match_the_per_move_formulation(batch):
    golden = json.loads((FIXTURES / "approx_eval_golden.json").read_text())
    assert answers(batch) == golden[batch]


def per_move_estimates(ia, seq, seq_len, mem, dur_all, r_all, q_all, mv):
    """The oracle: the round's estimate as it was computed per move, with one
    scalar gather per (walk, move, predecessor slot) at every window step, a
    per-move window table scattered at each step, and the re-pricing done
    per move."""
    import jax.numpy as jnp

    pred_mat, proc_time, io_cost = ia["pred_mat"], ia["proc_time"], ia["io_cost"]
    mv_task, mv_src_s, mv_dst_p, mv_dst_s, mv_cc, mv_valid = (
        mv[key] for key in ("task", "src_s", "dst_p", "dst_s", "cc", "valid"))
    W, M = mv_task.shape
    n_b, s_b, WIN = proc_time.shape[0], seq.shape[2], APPROX_WINDOW
    f64, INF = jnp.float64, jnp.inf
    wi = jnp.arange(W)

    def take_w(arr2d, idx):
        flat = idx.reshape(W, -1)
        return jnp.take_along_axis(arr2d, flat, axis=1).reshape(idx.shape)

    def new_seq_at(seq_dst, u, j, k, cc, i):
        t = i - (i > j)
        orig = t + ((~cc) & (t >= k))
        g = jnp.take_along_axis(
            seq_dst, jnp.clip(orig, 0, s_b - 1)[..., None], axis=-1)[..., 0]
        return jnp.where(i == j, u, g)

    def reprice(mem_w, u, b, blk_mat):
        blocks = blk_mat[jnp.clip(u, 0, n_b - 1)]            # (W, M, L)
        ok = blocks >= 0
        bsafe = jnp.where(ok, blocks, 0)
        memv = mem_w[wi[:, None, None], bsafe]               # (W, M, L)
        vals = jnp.where(ok, io_cost[bsafe, b[..., None], memv], 0.0)
        tot = jnp.zeros(vals.shape[:2], f64)
        for jj in range(vals.shape[2]):
            tot = tot + vals[:, :, jj]
        return tot

    seq_dst = jnp.take_along_axis(seq, mv_dst_p[:, :, None], axis=1)
    dur_u = take_w(dur_all, mv_task)
    q_u = take_w(q_all, mv_task)
    t_in_cc = reprice(mem, mv_task, mv_dst_p, ia["in_blk"])
    t_out_cc = reprice(mem, mv_task, mv_dst_p, ia["out_blk"])
    d_cc = t_in_cc + proc_time[mv_task, mv_dst_p] + t_out_cc
    dur_u = jnp.where(mv_cc, d_cc, dur_u)
    q_u = jnp.where(mv_cc, take_w(q_all, mv_task)
                    - take_w(dur_all, mv_task) + d_cc, q_u)
    finite = jnp.isfinite(dur_u)
    dst_len = jnp.take_along_axis(seq_len, mv_dst_p, axis=1)
    new_len = dst_len + mv_cc
    w_lo = jnp.where(mv_cc, mv_dst_s, jnp.minimum(mv_src_s, mv_dst_s))
    w_hi = jnp.minimum(new_len, w_lo + WIN)
    est = jnp.zeros((W, M), f64)
    xp = jnp.take_along_axis(
        seq_dst, jnp.clip(w_lo - 1, 0, s_b - 1)[..., None], axis=2)[..., 0]
    xp = jnp.clip(xp, 0, n_b - 1)
    prev_finish = jnp.where(
        w_lo > 0, take_w(r_all, xp) + take_w(dur_all, xp), 0.0)
    win_of = jnp.full((W, M, n_b + 1), -1, jnp.int8)
    win_heads = jnp.zeros((W, M, WIN), f64)
    mi = jnp.arange(M)[None, :]
    wim = jnp.broadcast_to(wi[:, None], (W, M))
    for s in range(WIN):
        idxp = w_lo + s
        act = mv_valid & (idxp < w_hi)
        x = new_seq_at(seq_dst, mv_task, mv_dst_s, mv_src_s, mv_cc, idxp)
        x = jnp.where(act, x, 0)
        preds = pred_mat[x]                                       # (W, M, Dp)
        pok = preds >= 0
        psafe = jnp.where(pok, preds, n_b)
        tpos = jnp.take_along_axis(win_of, psafe, axis=2)         # (W, M, Dp)
        in_win = tpos >= 0
        head_at = jnp.take_along_axis(
            win_heads, jnp.clip(tpos, 0, WIN - 1).astype(jnp.int32), axis=2)
        pclip = jnp.clip(preds, 0, n_b - 1)
        dsel = jnp.where(preds == mv_task[..., None],
                         dur_u[..., None], take_w(dur_all, pclip))
        f_win = head_at + dsel
        f_def = take_w(r_all, pclip) + take_w(dur_all, pclip)
        f = jnp.where(pok, jnp.where(in_win, f_win, f_def), -INF)
        head = jnp.maximum(prev_finish, f.max(axis=2))
        win_of = win_of.at[wim, mi, jnp.where(act, x, n_b)].set(jnp.int8(s))
        win_heads = win_heads.at[:, :, s].set(head)
        is_u = x == mv_task
        dx = jnp.where(is_u, dur_u, take_w(dur_all, x))
        qx = jnp.where(is_u, q_u, take_w(q_all, x))
        est = jnp.where(act, jnp.maximum(est, head + qx), est)
        prev_finish = jnp.where(act, head + dx, prev_finish)
    tailm = mv_valid & (w_hi < new_len)
    x_t = new_seq_at(seq_dst, mv_task, mv_dst_s, mv_src_s, mv_cc, w_hi)
    x_t = jnp.clip(jnp.where(tailm, x_t, 0), 0, n_b - 1)
    est = jnp.where(tailm, jnp.maximum(est, prev_finish + take_w(q_all, x_t)),
                    est)
    return jnp.where(finite & mv_valid, est, INF), finite


def walk_states(inst, seed: int):
    """Four packed walks of ``inst``: two greedy starts with their exact
    schedules, and two random core orders with random times; random tiers
    and tails throughout."""
    rng = np.random.default_rng(seed)
    ip = pack_instance(inst)
    sols = multiwalk_inits(inst, 2, seed)[0]
    st = pack_state(ip, sols, [exact_schedule(inst, s) for s in sols], seed)
    seq, seq_len = [np.concatenate([st[k]] * 2) for k in ("seq", "seq_len")]
    start, finish = [np.concatenate([st[k]] * 2) for k in ("start", "finish")]
    for w in (2, 3):
        seq[w], seq_len[w] = -1, 0
        cores = rng.integers(0, ip.p, ip.n)
        for c in range(ip.p):
            tasks = rng.permutation(np.nonzero(cores == c)[0])
            seq[w, c, :len(tasks)], seq_len[w, c] = tasks, len(tasks)
        start[w, :ip.n] = rng.uniform(0, 300, ip.n)
        finish[w, :ip.n] = start[w, :ip.n] + rng.uniform(1, 30, ip.n)
    mem = rng.integers(0, inst.n_mems, (4, ip.d_b)).astype(np.int32)
    q = rng.uniform(1, 300, (4, ip.n_b))
    return ip, seq, seq_len, mem, finish - start, start, q


def random_moves(ip, seq, seq_len, rng, m: int = 600) -> dict:
    """Per walk: N7 moves of a task within its core, change-core moves onto
    another core, and moves at arbitrary slots; about a fifth masked."""
    w_count = len(seq)
    out = {k: np.zeros((w_count, m), np.int32) for k in ("task", "src_s", "dst_p", "dst_s")}
    out["cc"] = np.zeros((w_count, m), bool)
    for w in range(w_count):
        for i in range(m):
            kind = i % 3
            c = rng.integers(0, ip.p)
            while kind < 2 and seq_len[w, c] == 0:
                c = rng.integers(0, ip.p)
            k = rng.integers(0, max(seq_len[w, c], 1))
            u = seq[w, c, k] if kind < 2 else rng.integers(0, ip.n)
            if kind == 0:     # N7: to another slot of its own core
                b, j, cc = c, rng.integers(0, seq_len[w, c]), False
            elif kind == 1:   # change core: into any slot of another core
                b = (c + 1 + rng.integers(0, ip.p - 1)) % ip.p
                j, cc = rng.integers(0, seq_len[w, b] + 1), True
            else:             # anywhere, padding slots included
                b, j, cc = rng.integers(0, ip.p), rng.integers(0, ip.s_b), rng.random() < 0.5
                k = rng.integers(0, ip.s_b)
            out["task"][w, i], out["src_s"][w, i] = u, k
            out["dst_p"][w, i], out["dst_s"][w, i], out["cc"][w, i] = b, j, cc
    out["valid"] = rng.random((w_count, m)) < 0.8
    return out


def window_cases(ip, seq, seq_len, mv) -> dict:
    """How often the moves' windows meet the cases the estimate must get
    right, counted in numpy from the same moves."""
    pred = ip.pred_mat
    counts = {"pred_in_window": 0, "moved_task_is_pred": 0, "masked": 0}
    for w in range(len(seq)):
        u, k, b, j, cc, ok = (mv[key][w] for key in
                              ("task", "src_s", "dst_p", "dst_s", "cc", "valid"))
        counts["masked"] += int((~ok).sum())
        new_len = seq_len[w][b] + cc
        lo = np.where(cc, j, np.minimum(k, j))
        hi = np.minimum(new_len, lo + APPROX_WINDOW)
        for i in np.nonzero(ok)[0]:
            idx = np.arange(lo[i], hi[i])
            win = _new_seq_at(seq[w][b[i]][None].repeat(len(idx), 0),
                              np.full(len(idx), u[i]), np.full(len(idx), j[i]),
                              np.full(len(idx), k[i]), np.full(len(idx), cc[i]),
                              idx)
            for s, x in enumerate(win):
                preds = pred[x][pred[x] >= 0]
                counts["pred_in_window"] += int(np.isin(preds, win[:s]).any())
                counts["moved_task_is_pred"] += int(u[i] in preds)
    return counts


@pytest.mark.parametrize("name", ["roomy40-16-0", "layered40-16-4", "fft8-16-2",
                                  "tight40_nonint"])
def test_estimates_match_the_per_move_oracle(name):
    import jax
    import jax.numpy as jnp

    inst = INSTANCES[name]
    ip, seq, seq_len, mem, dur, r, q = walk_states(inst, seed=len(name))
    mv = random_moves(ip, seq, seq_len, np.random.default_rng(7))
    cases = window_cases(ip, seq, seq_len, mv)
    assert all(v > 0 for v in cases.values()), cases
    assert (ip.pred_mat < 0).any()     # padded predecessor slots
    args = (ia_from_pack(ip), seq, seq_len, mem, dur, r, q, mv)
    with jax.enable_x64():
        args = jax.tree.map(jnp.asarray, args)
        est, finite = jax.jit(_window_estimates)(*args)
        est_o, finite_o = jax.jit(per_move_estimates)(*args)
    est, est_o = np.asarray(est), np.asarray(est_o)
    assert np.isfinite(est).sum() > len(est.ravel()) // 2
    assert np.array_equal(est.view(np.int64), est_o.view(np.int64))
    assert np.array_equal(np.asarray(finite), np.asarray(finite_o))


GATHER = re.compile(r"^\s*(?:ROOT )?\S+ = (\w+)\[([\d,]*)\]\S* gather\(.*"
                    r"slice_sizes=\{([\d,]*)\}")
CALLEES = re.compile(r"(?:to_apply|body|condition|calls)=([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def approx_eval_gathers(hlo: str) -> list:
    """``(output elements, slice sizes)`` of every gather under the
    ``ts_approx_eval`` scope of a lowered HLO module: its own op name, or
    that of an instruction that calls its computation, names the scope."""
    comps, comp = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and "=" not in line.split("(")[0]:
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[comp] = []
        elif comp is not None and " = " in line:
            comps[comp].append(line)
    called_from = {}
    for name, lines in comps.items():
        for line in lines:
            scoped = "ts_approx_eval" in "".join(OP_NAME.findall(line))
            for one, many in CALLEES.findall(line):
                for callee in ([one] if one else many.split(",")):
                    called_from.setdefault(callee.strip(), []).append((name, scoped))

    def under(name, seen=()):
        return any(scoped or (caller not in seen and under(caller, seen + (name,)))
                   for caller, scoped in called_from.get(name, []))

    out = []
    for name, lines in comps.items():
        for line in lines:
            m = GATHER.match(line)
            if m and ("ts_approx_eval" in line or under(name)):
                dims = [int(d) for d in m.group(2).split(",") if d]
                out.append((int(np.prod(dims)), [int(d) for d in m.group(3).split(",")]))
    return out


def test_no_per_slot_scalar_gather_in_the_approximate_evaluation():
    """The round program the serve engine launches (two instances, four
    walks, the critical set at full capacity), lowered at rehearsal size."""
    import jax

    insts = [INSTANCES["layered40-16-4"], INSTANCES["roomy40-16-0"]]
    batch = InstanceBatch.from_instances(insts)
    packs, w_count = list(batch.packs), 4
    states = []
    for s, (inst, ip) in enumerate(zip(insts, packs)):
        sols = multiwalk_inits(inst, w_count, s)[0]
        states.append(pack_state(ip, sols, [exact_schedule(inst, x) for x in sols], s))
    state = {k: np.stack([st[k] for st in states]) for k in states[0]}
    params, cfg = TSParams(), DeviceConfig(sync_every=1, crit_cap=batch.n_b)
    with jax.enable_x64():
        launch, _ = _get_launch(packs[0], w_count, params, batch.n_b, cfg, batch=2)
        series = jax.vmap(lambda _: _series_buffers(1, w_count))(np.arange(2))
        hlo = launch.lower(batch.arrays(), state, series).as_text(
            dialect="hlo", debug_info=True)
    ip = packs[0]
    m = batch.n_b * (2 + ip.p_b * (params.n_change_core_positions + 1))
    per_slot = w_count * m * ip.pred_mat.shape[1]
    gathers = approx_eval_gathers(hlo)
    assert len(gathers) > APPROX_WINDOW     # the scope was found
    assert [g for g in gathers if g[0] >= per_slot and max(g[1]) == 1] == []
