"""Run one benchmark cell once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (``bench.spec``).  The run:

1. refuses to measure on anything but a TPU with as many chips as the
   cell asks for (non-zero exit, no result);
2. keeps JAX's persistent compilation cache in ``.bench_cache/jax`` inside
   the checkout, so only a checkout's first run compiles;
3. draws the configuration's instance pool and the request seeds from
   ``--seed`` (``bench.instances``);
4. starts ``repro.serve.SolveService`` on the device backend with the
   configuration's deployment settings, warming every launch shape the
   pool has;
5. drives closed-loop traffic (``bench.load``); everything up to the
   window's opening is set-up;
6. after the window: reads the device's peak memory, shuts the service
   down without draining, and compares every answer due in the window
   with the plain reference (``bench.check``);
7. prints the comparison on standard error and, as the last line of
   standard output, one JSON object: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
   the per-layer ones), ``device``, ``breakdown`` (``--trace 1``) and
   ``check``.

``--rehearsal`` runs the same path on the CPU at the configuration's
tiny rehearsal size (``JAX_PLATFORMS=cpu``).  Its line says
``"rehearsal": true`` and carries its readings under ``readings``, never
under ``metrics``: a CPU number is not a device measurement.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE = ROOT / ".bench_cache"


class Refused(SystemExit):
    """The run cannot measure here: no result is printed."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny CPU run of the same path; prints no metrics")
    return ap.parse_args(argv)


def sized(cell, rehearsal: bool):
    """The configuration and traffic as run (the rehearsal overrides)."""
    cfg, traffic = dict(cell.config), dict(cell.traffic)
    if rehearsal:
        cfg.update(cfg.get("rehearsal", {}))
        traffic.update(traffic.get("rehearsal", {}))
    return cfg, traffic


def devices(chips: int, rehearsal: bool) -> list:
    import jax

    devs = jax.devices()
    want = "cpu" if rehearsal else "tpu"
    if devs[0].platform != want:
        raise Refused(f"bench: JAX's first device is {devs[0].platform!r}, "
                      f"this run needs {want!r}; no result")
    if len(devs) < chips:
        raise Refused(f"bench: {len(devs)} {want} device(s), the cell needs "
                      f"{chips}; no result")
    return devs[:chips]


def lowering_counter():
    """Count of programs lowered since the call (a fresh compile or a
    persistent-cache read each lower once; a warm call lowers nothing)."""
    import jax

    n = [0]

    def listen(name, _secs, **_kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: n[0]


def request_seeds(seed: int, n: int) -> list:
    import numpy as np

    from bench.instances import seed_words

    ss = np.random.SeedSequence(seed_words(seed) + [1])
    return [int(x) for x in np.random.default_rng(ss).integers(0, 2**31 - 1, n)]


async def serve(cfg, traffic, pool, lbs, args, trace_dir, lowered):
    from repro.core import Budget, TSParams
    from repro.serve import BatchPolicy, EngineConfig, SolveService, WarmSpec

    from bench.load import drive

    dep = cfg["deployment"]
    budget = Budget(time_limit=float(traffic["time_limit_s"]))
    walks = int(traffic["walks"])
    svc = SolveService(
        config=EngineConfig(backend=dep["backend"],
                            batch_sizes=tuple(dep["batch_sizes"]),
                            sync_every=int(dep["sync_every"]),
                            crit_cap=dep["crit_cap"]),
        policy=BatchPolicy(), params=TSParams(),
        warm=[WarmSpec(inst, walks, budget) for inst in pool])
    await svc.start()
    started = time.monotonic()
    try:
        out = await drive(svc, pool, lbs, clients=int(traffic["clients"]),
                          budget=budget, walks=walks,
                          request_seeds=request_seeds(args.seed, 4 * len(pool)),
                          seconds=args.seconds,
                          warmup_cuts=int(traffic["warmup_cuts"]),
                          due_wait=float(traffic["due_wait_s"]),
                          stall=float(traffic["stall_s"]),
                          trace_dir=trace_dir, lowered=lowered)
        out.peak_bytes = peak_bytes()
        out.started = started
    finally:
        await svc.shutdown(drain=False, timeout=120.0)
    return out


def peak_bytes() -> "int | None":
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()[:1]]
    vals = [s["peak_bytes_in_use"] for s in stats if "peak_bytes_in_use" in s]
    return max(vals) if vals else None


class Run:
    """What the metric readers see (``bench/metrics/__init__.py``)."""

    def __init__(self, window, setup_s, trace):
        self.window, self.setup_s, self.trace = window, setup_s, trace


def prepare(args):
    """The cell as run here, and the devices it runs on (or Refused)."""
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench.spec import load_cell

    cell = load_cell(args.workload)
    cfg, traffic = sized(cell, args.rehearsal)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ.setdefault("TPU_LOG_DIR", str(CACHE / "tpu_logs"))
    devs = devices(cell.chips, args.rehearsal)
    try:
        import repro.serve  # noqa: F401  the system under test
    except ImportError as e:
        raise Refused(f"bench: the program is not importable here: {e}") from e
    return cell, cfg, traffic, devs


def measure(cell, cfg, traffic, devs, args, t_start: float) -> dict:
    """One run of the cell: the result line, the answers compared
    (``items``) and the comparison's details."""
    from bench import check
    from bench.bounds import lower_bound
    from bench.instances import draw_pool, to_program
    from bench.trace import load, reduce
    from bench.window import Window, cuts

    lowered = lowering_counter()
    cases = draw_pool(cfg, args.seed, int(cfg["pool"]))
    pool = [to_program(c) for c in cases]
    lbs = [lower_bound(c) for c in cases]
    trace_dir = None
    if args.trace:
        trace_dir = str(CACHE / "trace" / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)

    out = asyncio.run(serve(cfg, traffic, pool, lbs, args, trace_dir, lowered))
    setup_s = out.opened - t_start
    window = Window.of(out.completions, out.opened, out.closed)

    # every answer due in the window: answered in it, or sent before it
    # opened (answered late, with an error, or never)
    in_window = {c.rid for c in window.completions}
    due = [c for c in out.completions
           if c.rid in in_window or c.sent < out.opened]
    errors = [c for c in due if c.error is not None]
    items, reports = [], []
    for c in due:
        if c.error is None:
            req, rep = out.reports[c.rid]
            reports.append(rep)
            items.append((cases[req.pool_index], check.answers_of(rep),
                          float(rep.initial_makespan), int(rep.iterations)))
    numbers, details = check.compare(items, missing=len(out.missing) + len(errors),
                                     compiles=out.compiles)

    trace = reduce(load(trace_dir)) if trace_dir else None
    run = Run(window, setup_s, trace)
    readings = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            readings[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.peak_bytes}
    if trace:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    line = {"correct": check.verdict(numbers),
            "attempted": len(due) + len(out.missing),
            "failed": len(errors) + len(out.missing)}
    if args.rehearsal:
        line.update(rehearsal=True, readings=readings)
    else:
        line["metrics"] = readings
    line["device"] = device
    if trace:
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    line["window"] = {"seconds": window.seconds, "cuts": window.n_cuts(),
                      "answered": len(window.answered)}
    line["check"] = {k: {"value": v, "limit": check.LIMITS[k]}
                     for k, v in numbers.items()}
    timeline = {
        "service_started_s": out.started - t_start,
        "window_opened_s": setup_s,
        "cuts": [[round(t - t_start, 3), len(g)] for t, g in cuts(out.completions)],
        "errors": sorted({c.error for c in out.completions if c.error})[:3],
    }
    return {"line": line, "items": items, "reports": reports,
            "details": details, "timeline": timeline}


def main(argv=None) -> int:
    args = parse(argv)
    cell, cfg, traffic, devs = prepare(args)
    res = measure(cell, cfg, traffic, devs, args, T_START)
    line = res["line"]
    print("timeline: " + json.dumps(res["timeline"]), file=sys.stderr)
    for d in res["details"]:
        print(f"check detail: {d}", file=sys.stderr)
    for k, v in line["check"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
