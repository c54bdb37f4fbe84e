"""Closed-loop load on ``repro.serve.SolveService``, as its users call it.

``clients`` clients each send a request, ``await`` its answer, and send
the next the moment it arrives (toolchain workers that each wait for
their schedule).  Client ``c``'s ``k``-th request solves pool instance
``(c + clients * k) mod len(pool)`` under ``Budget(time_limit=T)`` with
``walks`` walks and its own search seed, so a seed fixes every request's
content whatever the timing.

:func:`drive` runs the load, opens the window at the ``warmup_cuts``-th
cut completion, optionally traces the first whole cut after it, closes
the window as ``bench.window`` says, and waits up to ``due_wait`` seconds
for requests that were sent before the window opened (the ones due in
it).  Clients stop sending once the window has closed; in-flight work is
not drained.  Where no answer arrives for ``stall`` seconds after the
deadline, the window closes empty and the due requests count as missing.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import time

from bench.window import Completion, close_time, cuts

__all__ = ["Request", "Outcome", "drive"]

@dataclasses.dataclass(frozen=True)
class Request:
    pool_index: int
    seed: int


@dataclasses.dataclass
class Outcome:
    completions: list          # every Completion, in arrival order
    reports: dict              # rid -> (Request, report)
    opened: float
    closed: float
    missing: list              # due requests never answered
    compiles: int              # programs lowered inside the window
    started: float = 0.0       # when the service had started (set by the caller)
    peak_bytes: "int | None" = None  # device peak after the window


def _spans(on: bool):
    """``span(name)``: a profiler span where tracing, else nothing."""
    if not on:
        return lambda name: contextlib.nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def _wrap_engine(engine, span) -> None:
    """Host spans around the engine's per-cut calls, for the trace's gap
    attribution (the calls themselves are unchanged)."""
    for name in ("assemble", "execute"):
        fn = getattr(engine, name, None)
        if fn is None:
            continue

        def wrapped(*a, _fn=fn, _name=f"bench.engine.{name}", **kw):
            with span(_name):
                return _fn(*a, **kw)

        setattr(engine, name, wrapped)


async def drive(svc, pool_instances, lbs, *, clients: int, budget, walks: int,
                request_seeds, seconds: float, warmup_cuts: int,
                due_wait: float, stall: float, trace_dir: "str | None" = None,
                lowered=lambda: 0) -> Outcome:
    clock = time.monotonic
    span = _spans(trace_dir is not None)
    if trace_dir is not None:
        _wrap_engine(svc.engine, span)
    completions: list = []
    reports: dict = {}
    sent_at: dict = {}
    stop = False
    arrived = asyncio.Event()

    async def client(c: int):
        k = 0
        while not stop:
            idx = c + clients * k
            k += 1
            req = Request(idx % len(pool_instances),
                          request_seeds[idx % len(request_seeds)])
            err, rep, rr, m = None, None, None, {}
            with span("bench.submit"):
                sent = clock()
                try:
                    rid = await svc.submit(pool_instances[req.pool_index],
                                           budget, seed=req.seed, walks=walks)
                except Exception as e:  # refused at the door: a failed request
                    rid, err = -1 - idx, f"{type(e).__name__}: {e}"
            sent_at[rid] = (sent, req)
            if err is None:
                try:
                    rr = await svc.result(rid)
                    rep, m = rr.report, rr.metrics
                except Exception as e:  # recorded: a failed answer counts as missing
                    err = f"{type(e).__name__}: {e}"
            done = clock()
            with span("bench.result"):
                reports[rid] = (req, rep)
                completions.append(Completion(
                    rid=rid, sent=sent, done=done, cut=cut_of(rid, rr, m),
                    iterations=0 if rep is None else int(rep.iterations),
                    mk_over_lb=float("nan") if rep is None
                    else float(rep.makespan) / lbs[req.pool_index],
                    queue_wait=float(m.get("queue_wait", float("nan"))),
                    assemble_s=float(m.get("assemble_seconds", float("nan"))),
                    error=err))
                arrived.set()
            if rid < 0:
                return  # a refused client stops: the run is already failed

    async def next_arrival(timeout: float) -> None:
        arrived.clear()
        with contextlib.suppress(asyncio.TimeoutError):
            await asyncio.wait_for(arrived.wait(), timeout)

    tasks = [asyncio.create_task(client(c)) for c in range(clients)]
    try:
        # warm-up: the window opens at the warmup_cuts-th cut completion
        while len(cuts(completions)) < warmup_cuts:
            last = len(completions)
            await next_arrival(stall)
            if len(completions) == last:
                raise RuntimeError(f"no answer in {stall} s of warm-up")
        opened = cuts(completions)[warmup_cuts - 1][0]
        lowered_at_open = lowered()

        profiling, stretch = False, None
        if trace_dir is not None:
            import jax

            jax.profiler.start_trace(trace_dir, profiler_options=_profile_options())
            stretch = span("bench.traced")
            stretch.__enter__()
            profiling = True
            n_cuts_at_trace = len(cuts(completions))

        closed = None
        while closed is None:
            remaining = opened + seconds - clock()
            last = len(completions)
            await next_arrival(remaining if remaining > 0 else stall)
            if profiling and len(cuts(completions)) > n_cuts_at_trace:
                stretch.__exit__(None, None, None)
                jax.profiler.stop_trace()
                profiling = False
            if clock() >= opened + seconds:
                closed = close_time([t for t, _ in cuts(completions)], opened, seconds)
                if closed is None and remaining <= 0 and len(completions) == last:
                    closed = opened  # answers stopped coming: an empty window
        compiles = lowered() - lowered_at_open
        stop = True
        if profiling:
            stretch.__exit__(None, None, None)
            jax.profiler.stop_trace()

        # answers due in the window: every request sent before it opened
        def outstanding():
            answered = {c.rid for c in completions}
            return [rid for rid, (sent, _) in sent_at.items()
                    if sent < opened and rid not in answered]

        deadline = clock() + due_wait
        while outstanding() and clock() < deadline:
            await next_arrival(deadline - clock())
        missing = [sent_at[rid][1] for rid in outstanding()]
    finally:
        stop = True
        for t in tasks:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
    return Outcome(completions=list(completions), reports=reports,
                   opened=opened, closed=closed, missing=missing,
                   compiles=compiles)


def cut_of(rid: int, rr, metrics: dict):
    """The cut (batched launch) an answer came from: the instant the
    service cut it, on the service's clock (its ``submitted`` plus its
    ``queue_wait``).  A failed request is a cut of its own."""
    if rr is None or "queue_wait" not in metrics:
        return ("failed", rid)
    return round(rr.request.submitted + float(metrics["queue_wait"]), 6)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # no per-call Python events
    opts.host_tracer_level = 1     # user annotations: the bench.* spans
    return opts
