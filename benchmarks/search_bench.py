"""Tabu-iteration throughput: the PR-2 scalar-loop search, the PR-3
array-native multi-walk engine, and the PR-4 device-resident engine.

Lanes (``--backend``):

* ``numpy`` (default) — the PR-3 comparison: full tabu searches under equal
  parameters at Table-II scale, scalar-loop ``tabu_search`` baseline vs
  ``solve(inst, "tabu_multiwalk", walks=1)``.  Gates (full scale): engine
  ≥3× iteration throughput, and ``walks=8`` ≤ the single walk under an
  equal ``max_evals`` budget.  ``--smoke`` asserts the W=1 trajectory is
  *identical* to the legacy driver.
* ``suite`` — the PR-5 workload-suite lane: whole registered suites
  (``repro.instances``) swept on the numpy and device backends.  Each
  shape-bucket group runs through one vmapped ``solve_instances`` launch;
  the launch-cache counters must show at most one compile per bucket, and
  every row is normalized by the family-independent lower bound so quality
  is comparable across families.  Writes ``BENCH_suite.json`` and a
  ``search_bench_suite`` gate record to ``history.jsonl``.
* ``device`` — the PR-4 device engine lane.  Asserts the W=1 device
  trajectory is **bit-for-bit identical** to the legacy ``tabu_search``
  history (the parity gate), then measures steady-state walk-iteration
  throughput of ``device_multiwalk`` vs the numpy ``tabu_multiwalk`` at
  W=8 with jit compilation excluded (cold and warm runs are reported
  separately), and runs a whole row of instances through the vmapped
  ``solve_instances`` sweep (one compiled call per sync).  The ≥2×
  throughput gate is enforced on accelerator backends (TPU/GPU), where the
  fused program and the Pallas sweep pay off; on CPU the measured ratio is
  recorded but not gated — XLA's gather lowering loses to NumPy's C fancy
  indexing there (measured, documented in DESIGN.md §9), and failing the
  lane for it would only punish honest numbers.

Every run appends a machine-readable record (git sha, timestamp, gate
values) to ``results/bench/history.jsonl`` and writes
``results/bench/BENCH_search.json``.

    PYTHONPATH=src python -m benchmarks.search_bench                     # Table-II scale
    PYTHONPATH=src python -m benchmarks.search_bench --smoke             # CI-sized
    PYTHONPATH=src python -m benchmarks.search_bench --backend device    # device lane
    PYTHONPATH=src python -m benchmarks.search_bench --smoke --backend device
"""
from __future__ import annotations

import argparse
import dataclasses
import time

from repro.core import TSParams, random_instance, solve
from repro.core.greedy import STRATEGIES, construct_greedy
from repro.core.tabu import tabu_multiwalk, tabu_search

from .common import (
    append_history,
    certify_incumbents,
    emit,
    gate_compile_budget,
    save_json,
)


def throughput_params(max_iters: int, seed: int) -> TSParams:
    """Equal-params profile: iteration-bounded, nothing else binding."""
    return TSParams(max_unimproved=10**9, time_limit=10**9, top_k=10,
                    max_iters=max_iters, seed=seed)


def run_baseline(inst, params: TSParams):
    """PR-2-faithful scalar loop: legacy driver + scalar Alg-3 oracle.
    Construction is timed too, mirroring the engine path (solve() builds its
    walk inits inside the timed region)."""
    p = dataclasses.replace(params, mem_update_scalar=True)
    t0 = time.monotonic()
    init = construct_greedy(inst, "slack_first", rng=p.seed)
    res = tabu_search(inst, init, p)
    return res, time.monotonic() - t0


def run_engine(inst, params: TSParams, walks: int = 1):
    t0 = time.monotonic()
    rep = solve(inst, "tabu_multiwalk", walks=walks, params=params, seed=params.seed)
    return rep, time.monotonic() - t0


# --------------------------------------------------------------------------- #
# numpy lane (PR-3 gates, unchanged semantics)                                 #
# --------------------------------------------------------------------------- #
def numpy_lane(inst, args, n_tasks, n_data, iters, eq_evals, eq_unimproved):
    params = throughput_params(iters, args.seed)
    base_res, base_t = run_baseline(inst, params)
    eng_rep, eng_t = run_engine(inst, params, walks=1)
    base_ips = base_res.iterations / base_t
    eng_ips = eng_rep.iterations / eng_t
    speedup = eng_ips / base_ips
    payload = {
        "params": {"max_iters": iters, "top_k": params.top_k, "seed": args.seed},
        "baseline": {"iterations": base_res.iterations, "seconds": base_t,
                     "iters_per_s": base_ips, "makespan": base_res.best_makespan,
                     "n_exact_evals": base_res.n_exact_evals,
                     "n_approx_evals": base_res.n_approx_evals},
        "engine_w1": {"iterations": eng_rep.iterations, "seconds": eng_t,
                      "iters_per_s": eng_ips, "makespan": eng_rep.makespan,
                      "n_exact_evals": eng_rep.n_exact_evals,
                      "n_approx_evals": eng_rep.n_approx_evals},
        "speedup": speedup,
    }
    emit("search_baseline", 1e6 / max(base_ips, 1e-12), f"{base_ips:.2f} iters/s")
    emit("search_multiwalk_w1", 1e6 / max(eng_ips, 1e-12),
         f"{eng_ips:.2f} iters/s ({speedup:.1f}x)")

    # W=1 must retrace the legacy driver exactly (note: the baseline above
    # runs the *scalar* Alg-3 oracle, which is allocation-identical, so the
    # trajectories must already agree run-to-run)
    parity = (
        base_res.history == eng_rep.history
        and base_res.iterations == eng_rep.iterations
        and base_res.n_exact_evals == eng_rep.n_exact_evals
        and base_res.n_approx_evals == eng_rep.n_approx_evals
        and base_res.best_makespan == eng_rep.makespan
    )
    payload["w1_parity"] = parity
    if args.smoke and not parity:
        raise SystemExit(
            "W=1 tabu_multiwalk diverged from the legacy trajectory: "
            f"{base_res.history} vs {eng_rep.history}")

    # equal-max_evals budget: best of 8 walks vs the single walk.  Both runs
    # get the same cap; it is sized so the walks converge (max_unimproved)
    # before it binds — once walk 0 (which retraces the single walk) has
    # converged, its incumbent is locked and best-of-8 can only match or
    # beat the single walk.  The amortized Alg-3 profile keeps the stage
    # inside a couple of minutes.
    eq_params = TSParams(max_unimproved=eq_unimproved, time_limit=10**9,
                         top_k=10, mem_refresh_every=16,
                         seed=args.seed, max_evals=eq_evals)
    single, single_t = run_engine(inst, eq_params, walks=1)
    multi, multi_t = run_engine(inst, eq_params, walks=8)
    payload["equal_evals"] = {
        "max_evals": eq_evals,
        "single": {"makespan": single.makespan, "n_exact_evals": single.n_exact_evals,
                   "seconds": single_t, "stop_reason": single.stop_reason},
        "multi_w8": {"makespan": multi.makespan, "n_exact_evals": multi.n_exact_evals,
                     "seconds": multi_t, "stop_reason": multi.stop_reason,
                     "per_walk": [
                         {"init": w["init"], "best_makespan": w["best_makespan"]}
                         for w in multi.extras["per_walk"]
                     ]},
        "multi_le_single": bool(multi.makespan <= single.makespan + 1e-9),
    }
    emit("search_equal_evals", 0.0,
         f"W=8 {multi.makespan:.0f} vs W=1 {single.makespan:.0f} "
         f"under max_evals={eq_evals}")
    # post-hoc (untimed) certificate check on every lane incumbent
    payload["certified"] = certify_incumbents(
        [(inst, base_res.best, base_res.best_makespan),
         (inst, eng_rep.solution, eng_rep.makespan, eng_rep.feasible),
         (inst, single.solution, single.makespan, single.feasible),
         (inst, multi.solution, multi.makespan, multi.feasible)],
        "search_bench numpy lane")
    return payload


# --------------------------------------------------------------------------- #
# suite lane (PR-5 gates): whole workload suites through the sweep driver      #
# --------------------------------------------------------------------------- #
def suite_lane(args):
    """Sweep registered suites on the numpy and device backends.

    The device half runs every shape-bucket group through one vmapped
    ``solve_instances`` launch; the launch-cache counters must show at most
    one compile per bucket (the "compile once per bucket" gate).  Rows are
    normalized by the family-independent lower bounds so TS-vs-LB quality
    is comparable across families.
    """
    from repro.core import Budget
    from repro.instances import sweep

    if args.smoke:
        suites = ["smoke"]
        budget = Budget(max_iters=6, time_limit=60.0)
        walks = 2
    else:
        suites = ["table2", "trees_small", "fft_wide", "stencil_small"]
        budget = Budget(max_iters=40, time_limit=120.0)
        walks = 4

    payload = {"suites": {}}
    for name in suites:
        t0 = time.monotonic()
        rep_np = sweep(name, solver="tabu_multiwalk", backend="numpy",
                       budget=budget, walks=walks, seed=args.seed)
        rep_dev = sweep(name, backend="device", budget=budget, walks=walks,
                        seed=args.seed, device={"sync_every": 8})
        compiles_ok = rep_dev.compiles <= rep_dev.buckets
        payload["suites"][name] = {
            "numpy": {"families": rep_np.families,
                      "wall": rep_np.wall_time,
                      "rows": rep_np.rows},
            "device": {"families": rep_dev.families,
                       "wall": rep_dev.wall_time,
                       "buckets": rep_dev.buckets,
                       "compiles": rep_dev.compiles,
                       "compiles_per_bucket_ok": compiles_ok,
                       "launch_cache": rep_dev.launch_cache,
                       "rows": rep_dev.rows},
            "seconds": time.monotonic() - t0,
            "certified": all(r["certified"]
                             for r in rep_np.rows + rep_dev.rows),
        }
        mean_ratio = sum(f["mean_ratio"] for f in rep_dev.families.values()) \
            / max(1, len(rep_dev.families))
        emit(f"suite_{name}", 0.0,
             f"{len(rep_dev.rows)} instances, {rep_dev.buckets} buckets, "
             f"{rep_dev.compiles} compiles, mean mk/LB {mean_ratio:.2f}")
        if not compiles_ok:
            raise SystemExit(
                f"suite {name}: {rep_dev.compiles} device compiles for "
                f"{rep_dev.buckets} buckets — the sweep must compile at most "
                "once per shape bucket")
    return payload


# --------------------------------------------------------------------------- #
# device lane (PR-4 gates)                                                     #
# --------------------------------------------------------------------------- #
def device_lane(args, n_tasks, n_data, iters):
    import jax

    from repro.core.device_search import (MEM_UPDATE_DISABLED, DeviceConfig,
                                          device_multiwalk, solve_instances)

    platform = jax.default_backend()
    inst = random_instance(args.seed, n_tasks=n_tasks, n_data=n_data)
    parity_params = dataclasses.replace(
        throughput_params(iters, args.seed),
        mem_update_period=MEM_UPDATE_DISABLED)
    cfg = DeviceConfig(sync_every=max(8, iters))

    # -- parity gate: W=1 device trajectory == legacy tabu_search history -- #
    # The bit-for-bit contract covers runs that never enter the random
    # perturbation branch (device draws threefry, legacy PCG — DESIGN §9),
    # so the hard assertion is scoped on the drivers' perturbation counters.
    init = construct_greedy(inst, "slack_first", rng=args.seed)
    legacy = tabu_search(inst, init.copy(), parity_params)
    dev1 = device_multiwalk(inst, [init.copy()], parity_params, config=cfg)
    parity = (
        dev1.history == legacy.history
        and dev1.iterations == legacy.iterations
        and dev1.n_exact_evals == legacy.n_exact_evals
        and dev1.n_approx_evals == legacy.n_approx_evals
        and dev1.best_makespan == legacy.best_makespan
    )
    parity_strict = legacy.n_perturbations == 0 and dev1.n_perturbations == 0
    if parity_strict and not parity:
        raise SystemExit(
            "device W=1 trajectory diverged from the legacy driver on a "
            f"perturbation-free run: {legacy.history} vs {dev1.history}")
    if not parity_strict:
        print(f"# parity not gated: perturbation fired "
              f"(legacy {legacy.n_perturbations}, device {dev1.n_perturbations})")

    # -- throughput: W walks, steady state (compile excluded) -------------- #
    walks = 2 if args.smoke else 8
    inits = [construct_greedy(inst, STRATEGIES[w % 4], rng=args.seed + w)
             for w in range(walks)]
    t0 = time.monotonic()
    np_res = tabu_multiwalk(inst, [s.copy() for s in inits], parity_params)
    t_np = time.monotonic() - t0
    np_wis = walks * np_res.iterations / t_np
    t0 = time.monotonic()
    dev_cold = device_multiwalk(inst, [s.copy() for s in inits],
                                parity_params, config=cfg)
    t_cold = time.monotonic() - t0
    t0 = time.monotonic()
    dev_warm = device_multiwalk(inst, [s.copy() for s in inits],
                                parity_params, config=cfg)
    t_warm = time.monotonic() - t0
    dev_wis = walks * dev_warm.iterations / t_warm
    ratio = dev_wis / np_wis
    if (np_res.n_perturbations == 0 and dev_warm.n_perturbations == 0
            and dev_warm.history != np_res.history):
        raise SystemExit("device multiwalk trajectory diverged from numpy "
                         "on a perturbation-free run")

    # -- vmapped row sweep: one compiled call per sync over N instances ---- #
    n_row = 2 if args.smoke else 4
    row = [random_instance(args.seed + 100 + i, n_tasks=n_tasks, n_data=n_data)
           for i in range(n_row)]
    row_inits = [[construct_greedy(r, STRATEGIES[w % 4], rng=args.seed + w)
                  for w in range(walks)] for r in row]
    t0 = time.monotonic()
    row_res = solve_instances(row, row_inits, parity_params, config=cfg)
    t_row_cold = time.monotonic() - t0
    row_iters = sum(r.iterations for r in row_res)
    t0 = time.monotonic()
    row_res = solve_instances(row, row_inits, parity_params, config=cfg)
    t_row = time.monotonic() - t0
    row_wis = walks * sum(r.iterations for r in row_res) / t_row

    payload = {
        "platform": platform,
        "walks": walks,
        "w1_parity": parity,
        "w1_parity_strict": parity_strict,
        "perturbations": {"legacy": legacy.n_perturbations,
                          "device_w1": dev1.n_perturbations},
        "numpy_multiwalk": {"iterations": np_res.iterations, "seconds": t_np,
                            "walk_iters_per_s": np_wis},
        "device": {"iterations": dev_warm.iterations,
                   "cold_seconds": t_cold, "warm_seconds": t_warm,
                   "compile_seconds": getattr(dev_cold, "compile_seconds", 0.0),
                   "walk_iters_per_s": dev_wis},
        "throughput_ratio": ratio,
        "row_sweep": {"instances": n_row, "iterations": row_iters,
                      "cold_seconds": t_row_cold, "seconds": t_row,
                      "walk_iters_per_s": row_wis},
    }
    emit("search_device_parity", 0.0, "bit-for-bit vs legacy" if parity else "DIVERGED")
    emit("search_device_w%d" % walks, 1e6 / max(dev_wis, 1e-12),
         f"{dev_wis:.2f} walk-iters/s steady ({ratio:.2f}x numpy; "
         f"compile {payload['device']['compile_seconds']:.1f}s)")
    emit("search_device_row", 1e6 / max(row_wis, 1e-12),
         f"{n_row} instances vmapped: {row_wis:.2f} walk-iters/s")

    # the ≥2x gate is an accelerator claim ("scales up, never down"): the
    # fused while_loop and the Pallas sweep target TPU/GPU; on CPU the XLA
    # gather lowering measurably loses to NumPy's C fancy indexing, so the
    # ratio is recorded (history.jsonl) but only sanity-floored
    # this lane runs with mem updates disabled (parity_params), so the
    # incumbents are pre-Alg-3: every constraint except capacity rejects
    payload["certified"] = certify_incumbents(
        [(inst, legacy.best, legacy.best_makespan),
         (inst, np_res.best, np_res.best_makespan),
         (inst, dev_warm.best, float(dev_warm.best_makespan))]
        + [(ri, r.best, float(r.best_makespan))
           for ri, r in zip(row, row_res)],
        "search_bench device lane", enforce_capacity=False)
    gate = 2.0 if platform != "cpu" else 0.1
    payload["throughput_gate"] = gate
    if not args.smoke and ratio < gate:
        raise SystemExit(
            f"device engine at {ratio:.2f}x numpy below the {gate}x gate "
            f"on platform={platform}")
    return payload


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized instance; asserts trajectory parity")
    ap.add_argument("--backend", choices=("numpy", "device", "suite"),
                    default="numpy", help="which engine lane to run")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persist jit-compiled launches under DIR unless "
                         "JAX_COMPILATION_CACHE_DIR is set (default: the "
                         "checkout's .jax_cache); cold runs seed it, warm "
                         "runs load from it")
    args = ap.parse_args(argv)

    from repro.serve import enable_compilation_cache

    compile_cache = enable_compilation_cache(args.compile_cache)

    if args.smoke:
        n_tasks, n_data, iters, eq_evals, eq_unimproved = 40, 100, 8, 2000, 10
    else:
        n_tasks, n_data, iters, eq_evals, eq_unimproved = 250, 600, 30, 20000, 12

    payload = {"scale": {"n_tasks": n_tasks, "n_data": n_data,
                         "smoke": args.smoke},
               "backend": args.backend,
               "compile_cache": compile_cache}

    if args.backend == "suite":
        payload["suite_lane"] = suite_lane(args)
        path = save_json("BENCH_suite", payload)
        gates = {}
        for name, lane in payload["suite_lane"]["suites"].items():
            gates[f"{name}_compiles"] = lane["device"]["compiles"]
            gates[f"{name}_buckets"] = lane["device"]["buckets"]
            gates[f"{name}_compiles_per_bucket_ok"] = \
                lane["device"]["compiles_per_bucket_ok"]
            ratios = [f["mean_ratio"]
                      for f in lane["device"]["families"].values()]
            gates[f"{name}_mean_ratio"] = sum(ratios) / max(1, len(ratios))
        gates["certified"] = all(
            s["certified"] for s in payload["suite_lane"]["suites"].values())
        append_history("search_bench_suite", gates, scale=payload["scale"])
        print(f"wrote {path}  (suite sweep: "
              + ", ".join(payload["suite_lane"]["suites"]) + ")")
        return payload

    if args.backend == "device":
        payload["device_lane"] = device_lane(args, n_tasks, n_data, iters)
        path = save_json("BENCH_search_device", payload)
        lane = payload["device_lane"]
        # per-bucket compile budget: each jit-compiled launch shape is a
        # bucket (multiwalk launch; row sweep ≈ cold minus steady-state)
        budget_rec, breach = gate_compile_budget("search_bench_device", {
            f"multiwalk_w{lane['walks']}": lane["device"]["compile_seconds"],
            "row_sweep": max(0.0, lane["row_sweep"]["cold_seconds"]
                             - lane["row_sweep"]["seconds"]),
        })
        append_history("search_bench_device", {
            "w1_parity": lane["w1_parity"],
            "throughput_ratio": lane["throughput_ratio"],
            "row_walk_iters_per_s": lane["row_sweep"]["walk_iters_per_s"],
            "platform": lane["platform"],
            # cold-start accounting: with --compile-cache a second CI run
            # should show this dropping toward zero (persistent cache hit)
            "compile_seconds": lane["device"]["compile_seconds"],
            "compile_cache": compile_cache,
            "certified": lane["certified"],
            **budget_rec,
        }, scale=payload["scale"])
        print(f"wrote {path}  (device {lane['throughput_ratio']:.2f}x numpy, "
              f"parity={lane['w1_parity']})")
        if breach:
            raise SystemExit(breach)
        return payload

    inst = random_instance(args.seed, n_tasks=n_tasks, n_data=n_data)
    payload.update(numpy_lane(inst, args, n_tasks, n_data, iters,
                              eq_evals, eq_unimproved))
    path = save_json("BENCH_search", payload)
    append_history("search_bench", {
        "speedup": payload["speedup"],
        "w1_parity": payload["w1_parity"],
        "multi_le_single": payload["equal_evals"]["multi_le_single"],
        "certified": payload["certified"],
    }, scale=payload["scale"])
    print(f"wrote {path}  (iteration-throughput speedup: "
          f"{payload['speedup']:.1f}x, w1_parity={payload['w1_parity']})")
    if not args.smoke:
        if payload["speedup"] < 3.0:
            raise SystemExit("multi-walk engine below the 3x iteration-throughput gate")
        if not payload["equal_evals"]["multi_le_single"]:
            raise SystemExit("walks=8 worse than single walk under the equal-eval budget")
    return payload


if __name__ == "__main__":
    main()
