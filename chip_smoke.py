"""Bring-up smoke of the scheduler's main path on one TPU.

Drives the system once through the entry points a user calls, at the
paper's scale (Table II recipe: 200-300 tasks, 500-700 data blocks, 2 fast
and 8 general cores), and checks what comes out:

1. device: refuses to run unless JAX's first device is a TPU;
2. compile cache: placed by ``repro.serve.compile_cache`` before any compile;
3. requests: 8 ``random_layered`` instances drawn from ``--seed``, all in
   one launch signature;
4. serving: ``SolveService`` on the device backend, one warmed program
   (batch 4, 4 walks, iteration-bound budget), the 8 requests as two full
   cuts.  Every request must come back a device result that the independent
   ILP checker certifies, no better start than its own initial incumbent,
   with no poisoning, retry, failure or compile inside the traffic;
5. reference: the same 8 requests through the numpy engine; prints the
   device/numpy makespan ratio and whether the results are bit-identical
   (recorded, not gated: TPU f64 is emulated);
6. kernel: ``backend="jax"`` evaluation and a short ``backend="jax"`` tabu
   search, which on a TPU run the Pallas schedule-DP sweep compiled by
   Mosaic, checked against numpy within float32 tolerance.

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {...}}`` and appears only when every check held.
Any failed check raises, so the exit code is non-zero.

    python chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_REQUESTS = 8
WALKS = 4
BATCH = 4
SYNC_EVERY = 16
BUDGET_ITERS = 16        # one sync horizon: one launch per cut
KERNEL_ITERS = 4
KERNEL_CANDIDATES = 24
F32_RTOL = 1e-5


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


# --------------------------------------------------------------------------- #
# phases                                                                       #
# --------------------------------------------------------------------------- #
def require_tpu() -> dict:
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (first device is "
                         f"{dev.platform!r}); refusing to run elsewhere")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def paper_size() -> tuple[int, int]:
    """The middle of the paper's ranges (``benchmarks.common.scale``)."""
    from benchmarks.common import scale

    full = scale(full=True)
    return (sum(full.n_tasks) // 2, sum(full.n_data) // 2)


def build_requests(seed: int, n: int, n_tasks: int, n_data: int, budget):
    """The first ``n`` instances, in draw order from ``seed``, that share
    the first one's launch signature."""
    import numpy as np

    from repro.instances import generate
    from repro.serve import launch_signature

    insts, sig, k = [], None, 0
    while len(insts) < n:
        check(k < 50 * n, f"fewer than {n} of {k} draws share a signature")
        inst = generate("random_layered",
                        np.random.default_rng([seed, k]),
                        n_tasks=n_tasks, n_data=n_data)
        k += 1
        s = launch_signature(inst, WALKS, budget)
        sig = s if sig is None else sig
        if s == sig:
            insts.append(inst)
    return insts, sig, k


async def _serve(insts, seeds, budget, params):
    from repro.core.device_search import launch_cache_info
    from repro.serve import (
        BatchPolicy,
        EngineConfig,
        RequestResult,
        SolveService,
        WarmSpec,
    )

    cfg = EngineConfig(backend="device", batch_sizes=(BATCH,),
                       sync_every=SYNC_EVERY)
    svc = SolveService(config=cfg,
                       # cut on a full batch only: all requests are queued
                       # long before the wait bound
                       policy=BatchPolicy(max_batch=BATCH, max_wait=60.0),
                       params=params,
                       warm=[WarmSpec(insts[0], WALKS, budget)])
    t0 = time.monotonic()
    await svc.start()
    start_s = time.monotonic() - t0
    warm_cache = launch_cache_info()
    t0 = time.monotonic()
    rids = [await svc.submit(inst, budget, seed=s, walks=WALKS)
            for inst, s in zip(insts, seeds)]
    results = []
    for rid in rids:
        try:
            results.append(await svc.result(rid))
        except Exception as e:  # recorded and failed on below
            results.append(e)
    wall = time.monotonic() - t0
    metrics = svc.metrics()
    await svc.shutdown()
    for r in results:
        check(isinstance(r, RequestResult),
              f"a request did not resolve to a RequestResult: {r!r}")
    return results, metrics, start_s, wall, warm_cache


def serve_phase(insts, seeds, budget, params) -> list:
    import jax

    from repro.analysis.certify import certify_report

    results, metrics, start_s, wall, warm_cache = asyncio.run(
        _serve(insts, seeds, budget, params))
    warm = metrics["warmup"]
    check(warm["signatures"] == 1, f"warm-up saw {warm['signatures']} "
          "signatures, expected 1")
    misses = warm["per_signature"][0]["cache_delta"]["misses"]
    check(misses == 1, f"start() compiled {misses} engine programs, "
          "expected exactly 1")
    stats = jax.devices()[0].memory_stats() or {}
    emit("warmup", start_seconds=start_s,
         compile_seconds=warm["compile_seconds"],
         programs_compiled=misses,
         persistent_cache=warm.get("persistent_cache"),
         peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))

    feasible = 0
    for inst, rr in zip(insts, results):
        rep = rr.report
        check(rr.metrics["backend"] == "device",
              f"rid {rr.request.rid} served by {rr.metrics['backend']}")
        cert = certify_report(inst, rep)
        check(cert.ok, f"rid {rr.request.rid} failed ILP certification: "
              f"{cert.violations[:3]}")
        check(rep.makespan <= rep.initial_makespan,
              f"rid {rr.request.rid}: makespan {rep.makespan} above its "
              f"initial {rep.initial_makespan}")
        feasible += bool(rep.feasible)
        emit("request", rid=rr.request.rid, n_tasks=inst.n_tasks,
             n_data=inst.n_data, makespan=rep.makespan,
             initial_makespan=rep.initial_makespan,
             iterations=rep.iterations, feasible=bool(rep.feasible),
             latency_s=rr.metrics["latency"],
             queue_wait_s=rr.metrics["queue_wait"],
             solve_s=rr.metrics["solve_seconds"],
             batch_size=rr.metrics["batch_size"])
    res = metrics["resilience"]
    for key in ("poisoned_signatures", "retries", "failed"):
        check(res[key] == 0, f"resilience {key} = {res[key]}, expected 0")
    after = metrics["launch_cache"]
    check(after["misses"] == warm_cache["misses"],
          f"{after['misses'] - warm_cache['misses']} engine compiles inside "
          "the traffic, expected 0")
    stats = jax.devices()[0].memory_stats() or {}
    emit("serve", requests=len(results), wall_seconds=wall,
         solved_per_s=len(results) / wall, batches=metrics["batches"],
         cuts_by_reason=metrics["cuts_by_reason"],
         latency_p50=metrics.get("latency_p50"),
         latency_p99=metrics.get("latency_p99"),
         certified=len(results), feasible=feasible, resilience=res,
         launch_cache=after,
         peak_bytes_in_use=stats.get("peak_bytes_in_use", "not reported"))
    return results


def same_result(a, b) -> bool:
    import numpy as np

    return (a.makespan == b.makespan and a.history == b.history
            and a.iterations == b.iterations
            and np.array_equal(a.solution.assign, b.solution.assign)
            and np.array_equal(a.solution.mem, b.solution.mem)
            and a.solution.proc_seq == b.solution.proc_seq)


def reference_phase(insts, seeds, budget, params, served) -> None:
    from repro.core import solve

    identical = 0
    for inst, seed, rr in zip(insts, seeds, served):
        t0 = time.monotonic()
        ref = solve(inst, "tabu_multiwalk", walks=WALKS, budget=budget,
                    seed=seed, backend="numpy", params=params)
        same = same_result(rr.report, ref)
        identical += same
        emit("reference", rid=rr.request.rid, numpy_makespan=ref.makespan,
             device_over_numpy=rr.report.makespan / ref.makespan,
             bit_identical=same, numpy_seconds=time.monotonic() - t0)
    emit("parity", bit_identical=identical, of=len(insts))


def candidates(inst, seed: int, k: int):
    """A mixed acyclic/cyclic batch: a greedy solution and its neighbors."""
    from repro.core.greedy import construct_greedy
    from repro.core.solution import exact_schedule, heads_tails
    from repro.core.tabu import _cc_moves, _n7_moves, apply_move

    sol = construct_greedy(inst, "slack_first", rng=seed)
    sched = exact_schedule(inst, sol)
    r, _, _, crit = heads_tails(inst, sol, sched)
    moves = _n7_moves(sol, crit) + _cc_moves(inst, sol, crit, r,
                                             sched.start, 5)
    cands = [sol]
    for m in moves[: k - 1]:
        c = sol.copy()
        apply_move(c, m)
        cands.append(c)
    return cands


def kernel_phase(inst, seed: int, params) -> None:
    import numpy as np

    from repro.core import Budget, solve
    from repro.core.eval_batch import BatchEvaluator
    from repro.core.solution import exact_schedule
    from repro.kernels import schedule_dp as sdp

    impl = sdp.default_impl()
    cands = candidates(inst, seed, KERNEL_CANDIDATES)
    ref = BatchEvaluator(inst, backend="numpy").evaluate(cands, tails=True)
    jx = BatchEvaluator(inst, backend="jax").evaluate(cands, tails=True)
    check(np.array_equal(jx.feasible, ref.feasible),
          "jax and numpy evaluators disagree on feasibility")
    f = ref.feasible
    scale_ = float(ref.makespan[f].max())
    for name in ("makespan", "start", "q"):
        a, b = getattr(jx, name)[f], getattr(ref, name)[f]
        err = float(np.max(np.abs(a - b))) if a.size else 0.0
        check(np.allclose(a, b, rtol=F32_RTOL, atol=F32_RTOL * scale_),
              f"jax {name} off numpy by {err}")
    # a short search on the same kernel: its reported makespan is its own
    # float32 evaluation, which numpy's float64 schedule must reproduce;
    # its trajectory may leave numpy's at a float32 tie, so the numpy
    # search is printed beside it, not compared
    t0 = time.monotonic()
    kw = dict(budget=Budget(max_iters=KERNEL_ITERS), seed=seed, params=params)
    rep_j = solve(inst, "tabu", backend="jax", **kw)
    solve_s = time.monotonic() - t0
    mk64 = exact_schedule(inst, rep_j.solution).makespan
    check(np.isclose(rep_j.makespan, mk64, rtol=F32_RTOL),
          f"backend='jax' tabu reports {rep_j.makespan}, numpy schedules "
          f"its solution at {mk64}")
    rep_n = solve(inst, "tabu", backend="numpy", **kw)
    emit("kernel", impl=impl, candidates=len(cands),
         feasible=int(f.sum()), jax_makespan=rep_j.makespan,
         numpy_schedule_of_jax_solution=mk64,
         numpy_search_makespan=rep_n.makespan, jax_solve_seconds=solve_s)


def require_mosaic_sweep(inst) -> None:
    """``backend="jax"`` must pick the Pallas sweep here, and that sweep
    must compile to a Mosaic kernel (a ``tpu_custom_call``), not to the XLA
    lowering or the interpreter."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import schedule_dp as sdp

    impl = sdp.default_impl()
    check(impl == "pallas", f"backend='jax' picks sweep {impl!r} here, "
          "expected the Pallas kernel")
    n_p = sdp._LANES * -(-sdp.bucket(inst.n_tasks) // sdp._LANES)
    call = sdp._build_pallas_sweep(n_p, inst.n_tasks, True, False,
                                   "float32")
    g = jax.ShapeDtypeStruct((n_p, n_p), jnp.int32)
    r = jax.ShapeDtypeStruct((KERNEL_CANDIDATES, n_p), jnp.int32)
    d = jax.ShapeDtypeStruct((KERNEL_CANDIDATES, n_p), jnp.float32)
    hlo = call.lower(g, g, r, r, d).compile().as_text()
    check("tpu_custom_call" in hlo,
          "the Pallas sweep did not compile to a Mosaic kernel")


# --------------------------------------------------------------------------- #
# entry point                                                                  #
# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the request instances and searches")
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    device = require_tpu()
    emit("device", **device)

    from repro.core import Budget
    from repro.serve import enable_compilation_cache

    from benchmarks.serve_bench import serve_params

    emit("compile_cache", directory=enable_compilation_cache())

    n_tasks, n_data = paper_size()
    budget = Budget(max_iters=BUDGET_ITERS)
    insts, sig, draws = build_requests(args.seed, N_REQUESTS, n_tasks,
                                       n_data, budget)
    emit("requests", n=len(insts), draws=draws, n_tasks=n_tasks,
         n_data=n_data, walks=WALKS, budget=dataclasses.asdict(budget),
         signature=sig[:6])
    params = serve_params()
    seeds = [args.seed * 1000 + k for k in range(len(insts))]
    served = serve_phase(insts, seeds, budget, params)
    reference_phase(insts, seeds, budget, params, served)

    require_mosaic_sweep(insts[0])
    kernel_phase(insts[0], args.seed, params)

    emit("done", seconds=time.monotonic() - t_start)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
