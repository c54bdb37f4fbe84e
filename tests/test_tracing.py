"""The program's own tracing: host spans (``jax.profiler.TraceAnnotation``
under ``repro.``) on the serve, engine and search paths, and the named
scopes of the device round program.

The spans are read back from the ``.xplane.pb`` the profiler writes, on its
own clock; the scopes from the lowered program.  Scopes change op metadata
only, so the compiled round program, metadata stripped, is the one built
with every scope taken out.
"""
import asyncio
import contextlib
import dataclasses
import glob
import os
import pathlib
import re
import time

import pytest

jax = pytest.importorskip("jax")

from repro.core import Budget, TSParams, random_instance  # noqa: E402
from repro.core.api import multiwalk_inits  # noqa: E402
from repro.core.device_search import (  # noqa: E402
    REPAIRS,
    DeviceConfig,
    _round_loop,
    _series_buffers,
    pack_state,
    solve_instances,
)
from repro.core.greedy import construct_greedy  # noqa: E402
from repro.core.memory_update import ALG3, memory_update  # noqa: E402
from repro.core.solution import exact_schedule  # noqa: E402
from repro.instances.batch import ia_from_pack, pack_instance  # noqa: E402
from repro.instances.suites import load_npz  # noqa: E402
from repro.serve import (  # noqa: E402
    BatchPolicy,
    Engine,
    EngineConfig,
    RequestQueue,
    SolveService,
)
from repro.serve.batcher import CutBatch  # noqa: E402

# the rehearsal size of the benchmark's paper-scale configuration
INST = random_instance(0, n_tasks=40, n_data=96)
ASSEMBLE = ("repro.engine.inits", "repro.engine.pack")
EXECUTE = ("repro.search.prep", "repro.search.launch", "repro.search.readback",
           "repro.search.sync", "repro.search.finish", "repro.engine.fanout")
SCOPES = ("ts_round", "ts_move_gen", "ts_approx_eval", "ts_exact_eval",
          "ts_perturb", "ts_commit")
# tight (20% fast memory) and roomy instances of tests/test_feasible_best.py
TIERS = load_npz(str(pathlib.Path(__file__).parent / "fixtures"
                     / "feasible_best_instances.npz"))


def recorded(log_dir) -> list:
    """``(name, start_ns, end_ns, metadata)`` of every ``repro.`` span."""
    from jax.profiler import ProfileData

    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return out


def profiled(log_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return jax.profiler.trace(str(log_dir), profiler_options=opts)


def test_engine_and_search_spans_nest_and_carry_the_cut(tmp_path):
    """One cut of two requests through ``Engine.assemble`` and
    ``Engine.execute`` on the device backend, two launches of one round."""
    q = RequestQueue()
    reqs = [q.make_request(INST, Budget(max_iters=2), seed=s, walks=2)
            for s in (3, 4)]
    cut = CutBatch(signature=reqs[0].signature, requests=reqs, cut_at=0.0,
                   reason="full")
    engine = Engine(EngineConfig(backend="device", batch_sizes=(2,),
                                 sync_every=1))
    with profiled(tmp_path):
        results = engine.execute(engine.assemble(cut))
    assert [r.request.rid for r in results] == [r.rid for r in reqs]
    assert all("launch_cache" not in r.metrics for r in results)

    spans = recorded(tmp_path)
    names = [s[0] for s in spans]
    for name in ("repro.engine.assemble", "repro.engine.execute") \
            + ASSEMBLE + EXECUTE:
        assert name in names, name
    assert names.count("repro.engine.assemble") == 1
    assert names.count("repro.engine.execute") == 1
    assert names.count("repro.search.launch") == 2
    assert names.count("repro.search.readback") == 2

    head = reqs[0].rid
    assert all(meta.get("cut") == head for *_, meta in spans)
    for per_request in ("repro.engine.inits", "repro.engine.fanout"):
        assert sorted(meta["rid"] for n, *_, meta in spans
                      if n == per_request) == [r.rid for r in reqs]

    def inside(children, parent):
        (_, lo, hi, _), = [s for s in spans if s[0] == parent]
        for name, s, e, _ in spans:
            if name in children:
                assert lo <= s <= e <= hi, (name, parent)

    inside(ASSEMBLE, "repro.engine.assemble")
    inside(EXECUTE, "repro.engine.execute")


def test_service_spans_mark_submit_and_the_wait_for_the_lane(tmp_path):
    """Two requests cut one at a time: the second cut is assembled while
    the first still runs, so the dispatch thread waits for the lane."""
    async def run():
        svc = SolveService(config=EngineConfig(backend="numpy"),
                           policy=BatchPolicy(max_batch=1),
                           params=TSParams())
        execute = svc.engine.execute

        def slow_execute(*a, **kw):
            time.sleep(0.5)
            return execute(*a, **kw)

        svc.engine.execute = slow_execute
        await svc.start()
        try:
            rids = [await svc.submit(INST, Budget(max_iters=2), seed=s,
                                     walks=2) for s in (3, 4)]
            for rid in rids:
                await asyncio.wait_for(svc.result(rid), 60)
        finally:
            await svc.shutdown(timeout=60)
        return rids

    with profiled(tmp_path):
        rids = asyncio.run(run())
    spans = recorded(tmp_path)
    submits = [meta["rid"] for n, *_, meta in spans if n == "repro.serve.submit"]
    assert submits == rids
    waits = [meta["cut"] for n, *_, meta in spans if n == "repro.serve.lane_wait"]
    assert waits == [rids[1]]
    executes = sorted((s, meta["cut"]) for n, s, _, meta in spans
                      if n == "repro.engine.execute")
    assert [c for _, c in executes] == rids


META = re.compile(r', metadata=\{(?:[^{}"]|"[^"]*")*\}')
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def program_text(compiled) -> str:
    """The compiled HLO without op metadata and the source tables it
    points into: what the device runs."""
    out, skip = [], False
    for line in compiled.as_text().split("\n"):
        if line in TABLES:
            skip = True
        elif skip and not line.strip():
            skip = False
        elif not skip:
            out.append(META.sub("", line))
    return "\n".join(out)


def lower_round_program():
    sols, _ = multiwalk_inits(INST, 2, 0)
    scheds = [exact_schedule(INST, s) for s in sols]
    ip = pack_instance(INST)
    params, cfg = TSParams(), DeviceConfig(sync_every=2, crit_cap=ip.n_b)
    with jax.enable_x64():
        fn = jax.jit(lambda ia, st, series: _round_loop(
            ia, 2, params, ip.n_b, cfg.sync_every, cfg)(st, series))
        return fn.lower(ia_from_pack(ip), pack_state(ip, sols, scheds, 0),
                        _series_buffers(cfg.sync_every, 2))


def test_round_program_scopes_change_metadata_only(monkeypatch):
    lowered = lower_round_program()
    debug = lowered.as_text(debug_info=True)
    for scope in SCOPES:
        assert scope in debug, scope
    scoped = program_text(lowered.compile())
    assert "ts_round" not in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = lower_round_program()
    assert "ts_round" not in plain.as_text(debug_info=True)
    assert program_text(plain.compile()) == scoped


def solve_group(prefix: str, iters: int, cut=None) -> None:
    """Four instances of the fixture in one batch, one sync per round."""
    insts = [i for i in TIERS if i.name.startswith(prefix)]
    params = dataclasses.replace(TSParams(), max_iters=iters)
    inits = [multiwalk_inits(inst, 4, s)[0] for s, inst in enumerate(insts)]
    solve_instances(insts, inits, params,
                    config=DeviceConfig(sync_every=1, crit_cap=64),
                    seeds=list(range(len(insts))), cut=cut)


def test_alg3_spans_nest_in_their_cut_and_a_repair_falls_back(tmp_path):
    """Two rounds on tight instances: Algorithm 3 runs on every walk start
    (``prep``), on every walk after the first round (``sync``) and on the
    device bests over capacity (``finish``); one repair comes out worse
    than its walk's best feasible schedule, which is served instead."""
    before = REPAIRS.copy()
    with profiled(tmp_path):
        solve_group("fft8-16", iters=2, cut=7)
    delta = REPAIRS - before
    assert delta["walks"] == 16 and delta["infeasible"] >= delta["fallback"] >= 1

    spans = recorded(tmp_path)
    alg3 = [(s, e, meta) for n, s, e, meta in spans if n == "repro.search.alg3"]
    assert all(meta.get("cut") == 7 for *_, meta in alg3)
    parents = {name: [(s, e) for n, s, e, meta in spans
                      if n == name and meta.get("cut") == 7]
               for name in ("repro.search.prep", "repro.search.sync",
                            "repro.search.finish")}
    where = {name: 0 for name in parents}
    for s, e, _ in alg3:
        name, = [n for n, ivs in parents.items()
                 if any(lo <= s <= e <= hi for lo, hi in ivs)]
        where[name] += 1
    assert where["repro.search.prep"] == 16
    assert where["repro.search.sync"] == 16
    assert where["repro.search.finish"] == delta["infeasible"]


def test_no_repair_falls_back_on_roomy_memory():
    before = REPAIRS.copy()
    solve_group("roomy40", iters=1)
    delta = REPAIRS - before
    assert delta["walks"] == 16
    assert delta["infeasible"] == delta["fallback"] == 0


@pytest.mark.parametrize("name", ["fft8-16-2", "layered40-16-4"])
def test_alg3_counts_alike_on_both_paths(name):
    inst = next(i for i in TIERS if i.name == name)
    sol = construct_greedy(inst, "slack_first", rng=0)
    counts = []
    for scalar in (False, True):
        before = ALG3.copy()
        out = memory_update(inst, sol, refresh_every=8, scalar=scalar)
        counts.append((ALG3 - before, out.mem.tolist()))
    assert counts[0] == counts[1]
    delta = counts[0][0]
    assert delta["calls"] == 1 and delta["blocks"] >= delta["refused"] > 0
