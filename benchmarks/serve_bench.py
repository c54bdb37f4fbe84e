"""Serving throughput/latency: the scheduling-solve service under Poisson
traffic (``repro.serve``).

A mixed-family request trace (paper-recipe ``random_layered`` +
``out_tree`` MDFGs) arrives with Poisson gaps at the asyncio front-end;
the service cuts same-signature batches continuously and runs them from
the warm launch pool.  Against it, two sequential solo baselines at the
exact same per-request (seed, walks, budget):

* ``seq_cold`` — naive solo ``solve()`` loop, per-instance launch shapes,
  jit compiles included: life without the serving subsystem;
* ``seq_warm`` — the same loop re-run with every program already compiled:
  the honest steady-state sequential throughput the gate compares against.

Gates (device lane): every served request's final result is **bit-
identical** to its solo ``seq_warm`` solve (same seed/budget/backend);
served solved-instances/s ≥ the ``seq_cold`` baseline at equal quality
(mean makespan/LB is identical by parity — recorded on both sides) — the
"no compile storms under traffic" claim the warm pool + quantized
signatures exist for, and it must hold everywhere; and anytime incumbents
streamed for at least one request.  The served ≥ ``seq_warm`` ratio is
additionally gated on accelerator platforms (TPU/GPU), where lock-step
vmap compute pays off; on CPU it is recorded but not gated — XLA executes
the batch essentially serially there and signature-pinned widths cost
extra per instance (same CPU stance as ``search_bench``'s device lane,
DESIGN.md §9/§11).  The numpy lane records the same trace served through
per-request numpy solves (parity gated, throughput recorded but not gated
— there is nothing to batch).

Writes ``BENCH_serve.json`` and appends a ``serve`` record to
``results/bench/history.jsonl`` (p50/p99 latency, throughputs, warmup
compile seconds — cold-vs-warm compile tracking for the persistent
compilation cache).

    PYTHONPATH=src python -m benchmarks.serve_bench --smoke
    PYTHONPATH=src python -m benchmarks.serve_bench --smoke --backend numpy
    PYTHONPATH=src python -m benchmarks.serve_bench --compile-cache results/jax_cache
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time

import numpy as np

from repro.core import TSParams, solve
from repro.core.api import Budget
from repro.instances import generate, lower_bound
from repro.serve import (
    BatchPolicy,
    EngineConfig,
    SolveService,
    WarmSpec,
    launch_signature,
)

from .common import (
    append_history,
    certify_incumbents,
    emit,
    gate_compile_budget,
    save_json,
)


@dataclasses.dataclass(frozen=True)
class Profile:
    families: tuple            # ((family, gen_kwargs), ...)
    n_requests: int
    walks: int
    budget: Budget
    rate: float                # Poisson arrivals per second
    batch_sizes: tuple
    sync_every: int
    crit_cap: int


def profile(smoke: bool) -> Profile:
    if smoke:
        return Profile(
            families=(("random_layered", {"n_tasks": 40, "n_data": 100}),
                      ("out_tree", {"n_tasks": 40})),
            n_requests=8, walks=2, budget=Budget(max_iters=6),
            rate=100.0, batch_sizes=(4,), sync_every=8, crit_cap=32)
    return Profile(
        families=(("random_layered", {"n_tasks": 70, "n_data": 160}),
                  ("out_tree", {"n_tasks": 70}),
                  ("fft", {"width": 16, "stages": 4})),
        n_requests=36, walks=4, budget=Budget(max_iters=20),
        rate=4.0, batch_sizes=(1, 2, 4, 8), sync_every=8, crit_cap=64)


def serve_params() -> TSParams:
    """Throughput-profile search knobs: iteration-bound budgets bind, so
    every request in a batch runs the same round count (no divergence
    waste) and trajectories are deterministic."""
    from repro.core.device_search import MEM_UPDATE_DISABLED

    return TSParams(max_unimproved=10**9, time_limit=1e9, top_k=5,
                    mem_update_period=MEM_UPDATE_DISABLED)


def build_trace(prof: Profile, seed: int):
    """Deterministic mixed-family trace with Poisson arrival offsets."""
    rng = np.random.default_rng(seed)
    items = []
    for k in range(prof.n_requests):
        fam, kw = prof.families[k % len(prof.families)]
        inst = generate(fam, np.random.default_rng(10_000 * seed + k), **kw)
        items.append({"family": fam, "instance": inst, "seed": seed + k})
    arrivals = np.cumsum(rng.exponential(1.0 / prof.rate,
                                         size=len(items)))
    return items, arrivals


def solo_method(backend: str) -> str:
    return "tabu_device" if backend == "device" else "tabu_multiwalk"


def run_solo(item, prof: Profile, params: TSParams, backend: str):
    kw = {}
    if backend == "device":
        kw["device"] = {"sync_every": prof.sync_every,
                        "crit_cap": prof.crit_cap}
    return solve(item["instance"], solo_method(backend), walks=prof.walks,
                 budget=prof.budget, seed=item["seed"], params=params, **kw)


def sequential_baseline(items, prof, params, backend):
    """Two passes of the solo loop: pass 1 pays every per-instance jit
    compile (``seq_cold``); pass 2 is steady state (``seq_warm``) and its
    reports double as the bit-parity references."""
    t0 = time.monotonic()
    for item in items:
        run_solo(item, prof, params, backend)
    t_cold = time.monotonic() - t0
    t0 = time.monotonic()
    reports = [run_solo(item, prof, params, backend) for item in items]
    t_warm = time.monotonic() - t0
    return reports, t_cold, t_warm


async def run_service(items, arrivals, prof, params, backend, cache_dir):
    cfg = EngineConfig(backend=backend, sync_every=prof.sync_every,
                       crit_cap=prof.crit_cap,
                       batch_sizes=prof.batch_sizes,
                       compilation_cache_dir=cache_dir)
    # declare the traffic: one WarmSpec per unique signature in the trace
    warm, seen = [], set()
    for item in items:
        sig = launch_signature(item["instance"], prof.walks, prof.budget)
        if sig not in seen:
            seen.add(sig)
            warm.append(WarmSpec(item["instance"], prof.walks, prof.budget))
    svc = SolveService(
        config=cfg,
        policy=BatchPolicy(max_batch=max(prof.batch_sizes),
                           max_wait=0.05),
        params=params, warm=warm)
    await svc.start()

    events: "dict[int, int]" = {}

    async def drain(rid):
        events[rid] = 0
        async for _ev in svc.stream_incumbents(rid):
            events[rid] += 1

    rids, drains = [], []
    t0 = time.monotonic()
    for k, item in enumerate(items):
        now = time.monotonic() - t0
        if arrivals[k] > now:
            await asyncio.sleep(arrivals[k] - now)
        rid = await svc.submit(item["instance"], prof.budget,
                               seed=item["seed"], walks=prof.walks)
        rids.append(rid)
        drains.append(asyncio.ensure_future(drain(rid)))
    results = [await svc.result(r) for r in rids]
    wall = time.monotonic() - t0
    await asyncio.gather(*drains)
    metrics = svc.metrics()
    await svc.shutdown()
    return results, wall, metrics, events, len(seen)


def report_parity(a, b) -> bool:
    return (a.makespan == b.makespan
            and a.history == b.history
            and a.iterations == b.iterations
            and a.n_exact_evals == b.n_exact_evals
            and a.n_approx_evals == b.n_approx_evals
            and np.array_equal(a.solution.assign, b.solution.assign)
            and np.array_equal(a.solution.mem, b.solution.mem)
            and a.solution.proc_seq == b.solution.proc_seq)


def lane(items, arrivals, prof, params, backend, cache_dir):
    platform = "host"
    if backend == "device":
        import jax

        platform = jax.default_backend()
    solo_reports, t_cold, t_warm = sequential_baseline(
        items, prof, params, backend)
    served, wall, metrics, events, n_sigs = asyncio.run(run_service(
        items, arrivals, prof, params, backend, cache_dir))

    n = len(items)
    parity = [report_parity(rr.report, solo_reports[k])
              for k, rr in enumerate(served)]
    lbs = [lower_bound(item["instance"]) for item in items]
    ratio_served = float(np.mean(
        [rr.report.makespan / lb for rr, lb in zip(served, lbs)]))
    ratio_solo = float(np.mean(
        [rep.makespan / lb for rep, lb in zip(solo_reports, lbs)]))
    lat = sorted(rr.metrics["latency"] for rr in served)
    payload = {
        "requests": n,
        "platform": platform,
        "signatures": n_sigs,
        "families": sorted({item["family"] for item in items}),
        "walks": prof.walks,
        "budget": dataclasses.asdict(prof.budget),
        "sequential": {"cold_seconds": t_cold, "warm_seconds": t_warm,
                       "cold_solved_per_s": n / t_cold,
                       "warm_solved_per_s": n / t_warm,
                       "mean_mk_over_lb": ratio_solo},
        "served": {"wall_seconds": wall, "solved_per_s": n / wall,
                   "latency_p50": lat[len(lat) // 2],
                   "latency_p99": lat[min(n - 1, int(0.99 * n))],
                   "mean_mk_over_lb": ratio_served,
                   "mean_batch_size": metrics["mean_batch_size"],
                   "cuts_by_reason": metrics["cuts_by_reason"],
                   "warmup_compile_seconds":
                       metrics["warmup"].get("compile_seconds", 0.0),
                   "warmup_per_signature":
                       metrics["warmup"].get("per_signature", []),
                   "launch_cache": metrics.get("launch_cache"),
                   "incumbent_events": sum(events.values()),
                   "requests_with_events":
                       sum(1 for v in events.values() if v > 0)},
        "throughput_ratio_vs_warm": (n / wall) / (n / t_warm),
        "throughput_ratio_vs_cold": (n / wall) / (n / t_cold),
        "parity": all(parity),
        "parity_per_request": parity,
        # post-hoc (untimed) certificate check on every served incumbent;
        # the engine additionally certifies inline when sanitize mode is on
        # (rr.metrics["certified"]) — this field gates the bench record
        "certified": certify_incumbents(
            [(item["instance"], rr.report.solution, rr.report.makespan,
              rr.report.feasible)
             for item, rr in zip(items, served)],
            f"serve bench {backend} lane"),
    }
    emit(f"serve_{backend}_p50", payload["served"]["latency_p50"] * 1e6,
         f"p99 {payload['served']['latency_p99']*1e3:.0f}ms, "
         f"{n / wall:.2f} solved/s")
    emit(f"serve_{backend}_throughput", 1e6 / max(n / wall, 1e-12),
         f"{payload['throughput_ratio_vs_warm']:.2f}x seq-warm, "
         f"{payload['throughput_ratio_vs_cold']:.2f}x seq-cold")
    return payload


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace (8 requests, 2 families)")
    ap.add_argument("--backend", choices=("device", "numpy", "both"),
                    default="both")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compile-cache", default=None, metavar="DIR",
                    help="persist jit-compiled launches under DIR unless "
                         "JAX_COMPILATION_CACHE_DIR is set (default: the "
                         "checkout's .jax_cache)")
    args = ap.parse_args(argv)

    prof = profile(args.smoke)
    params = serve_params()
    items, arrivals = build_trace(prof, args.seed)
    payload = {"smoke": args.smoke, "seed": args.seed,
               "profile": {"n_requests": prof.n_requests,
                           "rate": prof.rate, "walks": prof.walks,
                           "batch_sizes": list(prof.batch_sizes),
                           "sync_every": prof.sync_every},
               "lanes": {}}

    backends = ("device", "numpy") if args.backend == "both" \
        else (args.backend,)
    for backend in backends:
        payload["lanes"][backend] = lane(items, arrivals, prof, params,
                                         backend, args.compile_cache)

    path = save_json("BENCH_serve", payload)
    gates = {}
    for backend, ln in payload["lanes"].items():
        gates[f"{backend}_parity"] = ln["parity"]
        gates[f"{backend}_platform"] = ln["platform"]
        gates[f"{backend}_throughput_ratio_vs_warm"] = \
            ln["throughput_ratio_vs_warm"]
        gates[f"{backend}_throughput_ratio_vs_cold"] = \
            ln["throughput_ratio_vs_cold"]
        gates[f"{backend}_latency_p50"] = ln["served"]["latency_p50"]
        gates[f"{backend}_latency_p99"] = ln["served"]["latency_p99"]
        gates[f"{backend}_solved_per_s"] = ln["served"]["solved_per_s"]
        gates[f"{backend}_warmup_compile_seconds"] = \
            ln["served"]["warmup_compile_seconds"]
        gates[f"{backend}_certified"] = ln["certified"]
    # per-signature compile-second budget: each warm-pool signature is one
    # bucket; the breach is raised only after the history record lands
    compile_buckets = {
        f"{backend}:{'x'.join(map(str, ent['bucket_key']))}":
            ent["compile_seconds"]
        for backend, ln in payload["lanes"].items()
        for ent in ln["served"]["warmup_per_signature"]
    }
    budget_rec, breach = gate_compile_budget("serve", compile_buckets)
    gates.update(budget_rec)
    append_history("serve", gates, profile=payload["profile"])
    print(f"wrote {path}")
    if breach:
        raise SystemExit(breach)

    for backend, ln in payload["lanes"].items():
        if not ln["parity"]:
            raise SystemExit(
                f"serve {backend}: a served result diverged from its solo "
                f"solve (per-request: {ln['parity_per_request']})")
        if ln["served"]["incumbent_events"] < 1:
            raise SystemExit(
                f"serve {backend}: no anytime incumbent events streamed")
    dev = payload["lanes"].get("device")
    if dev is not None:
        if dev["throughput_ratio_vs_cold"] < 1.0:
            raise SystemExit(
                "batched device serving at "
                f"{dev['throughput_ratio_vs_cold']:.2f}x the cold "
                "sequential baseline — the warm pool must beat per-request "
                "compile storms")
        if dev["platform"] != "cpu" and dev["throughput_ratio_vs_warm"] < 1.0:
            raise SystemExit(
                "batched device serving at "
                f"{dev['throughput_ratio_vs_warm']:.2f}x sequential warm "
                f"throughput on platform={dev['platform']} — continuous "
                "batching must not lose to warm solo solves off-CPU")
    return payload


if __name__ == "__main__":
    main()
