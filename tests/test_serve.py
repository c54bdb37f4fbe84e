"""Serve subsystem: fake-clock batch cutting, launch-signature grouping,
queue semantics, engine batch assembly, and end-to-end service behaviour.

The service tests run the numpy backend so they stay in the fast tier-1
lane; the device-backend parity test (served report bit-identical to a solo
``solve()``) is ``@pytest.mark.slow`` since it compiles launches.
"""
import asyncio

import numpy as np
import pytest

from repro.core import Budget, TSParams, random_instance, solve
from repro.serve import (
    Batcher,
    BatchPolicy,
    Engine,
    EngineConfig,
    RequestQueue,
    ServiceClosed,
    SolveService,
    WarmSpec,
    launch_signature,
)


class FakeClock:
    """Deterministic queue clock: time moves only via ``advance``."""

    def __init__(self, t: float = 0.0):
        self.t = float(t)

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def small_instance(seed=0, **kw):
    kw.setdefault("n_tasks", 24)
    kw.setdefault("n_data", 60)
    return random_instance(seed, **kw)


INST = small_instance()
BUDGET = Budget(max_iters=4)


# --------------------------------------------------------------------------- #
# launch signatures
# --------------------------------------------------------------------------- #

def test_signature_equal_for_identical_request_shape():
    assert launch_signature(INST, 2, BUDGET) == launch_signature(INST, 2, BUDGET)


def test_signature_splits_on_walks_and_budget():
    base = launch_signature(INST, 2, BUDGET)
    assert launch_signature(INST, 4, BUDGET) != base
    assert launch_signature(INST, 2, Budget(max_iters=99)) != base


def test_signature_splits_on_instance_shape_class():
    big = small_instance(1, n_tasks=80, n_data=200)
    assert launch_signature(big, 2, BUDGET) != launch_signature(INST, 2, BUDGET)


# --------------------------------------------------------------------------- #
# queue
# --------------------------------------------------------------------------- #

def test_queue_fifo_take_and_absolute_deadline():
    clk = FakeClock(10.0)
    q = RequestQueue(clock=clk)
    r0 = q.submit(INST, BUDGET, seed=0, deadline=3.0)
    clk.advance(1.0)
    r1 = q.submit(INST, BUDGET, seed=1)
    assert r0.deadline == pytest.approx(13.0) and r1.deadline is None
    assert r0.submitted == 10.0 and r1.submitted == 11.0
    sig = r0.signature
    assert r1.signature == sig and len(q) == 2
    assert [r.rid for r in q.take(sig, 1)] == [r0.rid]
    assert [r.rid for r in q.take(sig, 5)] == [r1.rid]
    assert len(q) == 0 and q.take(sig, 1) == []


def test_queue_submit_after_close_raises():
    q = RequestQueue(clock=FakeClock())
    q.submit(INST, BUDGET)
    q.close()
    assert q.closed
    with pytest.raises(ServiceClosed):
        q.submit(INST, BUDGET)
    assert len(q) == 1  # pending requests survive close (drain semantics)


# --------------------------------------------------------------------------- #
# batcher: fake-clock cut conditions
# --------------------------------------------------------------------------- #

def make_batcher(clk, **policy_kw):
    q = RequestQueue(clock=clk)
    policy_kw.setdefault("max_batch", 4)
    policy_kw.setdefault("max_wait", 0.5)
    policy_kw.setdefault("deadline_slack", 0.25)
    return q, Batcher(q, BatchPolicy(**policy_kw))


def test_cut_on_full():
    clk = FakeClock()
    q, b = make_batcher(clk, max_batch=3)
    for s in range(3):
        q.submit(INST, BUDGET, seed=s)
    cut = b.cut()
    assert cut is not None and cut.reason == "full" and len(cut) == 3
    assert b.cut() is None and b.cuts_by_reason == {"full": 1}


def test_cut_on_age_after_max_wait():
    clk = FakeClock()
    q, b = make_batcher(clk, max_wait=0.5)
    q.submit(INST, BUDGET, seed=0)
    assert b.cut() is None  # too young, device busy
    clk.advance(0.49)
    assert b.cut() is None
    clk.advance(0.02)
    cut = b.cut()
    assert cut is not None and cut.reason == "age" and len(cut) == 1


def test_cut_on_deadline_within_slack():
    clk = FakeClock()
    # max_wait huge: only the deadline can trigger this cut
    q, b = make_batcher(clk, max_wait=1e9, deadline_slack=0.25)
    q.submit(INST, BUDGET, seed=0, deadline=2.0)
    assert b.cut() is None
    clk.advance(1.74)  # deadline 0.26 away: still outside slack
    assert b.cut() is None
    clk.advance(0.02)  # 0.24 away: inside slack
    cut = b.cut()
    assert cut is not None and cut.reason == "deadline"


def test_cut_when_device_idle_respects_eagerness():
    clk = FakeClock()
    q, b = make_batcher(clk, max_wait=1e9)
    q.submit(INST, BUDGET, seed=0)
    assert b.cut(device_idle=False) is None
    cut = b.cut(device_idle=True)
    assert cut is not None and cut.reason == "idle"

    q2, b2 = make_batcher(clk, max_wait=1e9, eager_when_idle=False)
    q2.submit(INST, BUDGET, seed=0)
    assert b2.cut(device_idle=True) is None


def test_cut_drains_after_close():
    clk = FakeClock()
    q, b = make_batcher(clk, max_wait=1e9)
    q.submit(INST, BUDGET, seed=0)
    q.close()
    cut = b.cut()
    assert cut is not None and cut.reason == "drain"
    assert b.cut() is None and len(q) == 0


def test_cut_never_mixes_signatures_and_serves_oldest_head_first():
    clk = FakeClock()
    q, b = make_batcher(clk, max_batch=8, max_wait=0.1)
    a0 = q.submit(INST, BUDGET, seed=0, walks=2)
    clk.advance(0.01)
    b0 = q.submit(INST, BUDGET, seed=1, walks=4)  # different signature
    clk.advance(0.01)
    a1 = q.submit(INST, BUDGET, seed=2, walks=2)
    clk.advance(0.2)  # both groups age-ready; walks=2 head is oldest
    first = b.cut()
    assert first.reason == "age"
    assert [r.rid for r in first.requests] == [a0.rid, a1.rid]
    assert all(r.signature == first.signature for r in first.requests)
    second = b.cut()
    assert [r.rid for r in second.requests] == [b0.rid]
    assert second.signature != first.signature


def test_next_cut_time_is_min_of_age_and_deadline_horizons():
    clk = FakeClock()
    q, b = make_batcher(clk, max_wait=0.5, deadline_slack=0.25)
    assert b.next_cut_time() is None
    q.submit(INST, BUDGET, seed=0)  # age-ready at t=0.5
    assert b.next_cut_time() == pytest.approx(0.5)
    q.submit(INST, BUDGET, seed=1, deadline=0.6)  # deadline-ready at 0.35
    assert b.next_cut_time() == pytest.approx(0.35)


# --------------------------------------------------------------------------- #
# engine: batch assembly (host-side, no compile)
# --------------------------------------------------------------------------- #

def test_assemble_pads_to_quantized_size_and_pins_bucket_key():
    clk = FakeClock()
    q = RequestQueue(clock=clk)
    b = Batcher(q, BatchPolicy(max_batch=3, max_wait=0.0))
    eng = Engine(EngineConfig(backend="device", batch_sizes=(4,)),
                 params=TSParams())

    for s in range(3):
        q.submit(INST, BUDGET, seed=s)
    asm3 = eng.assemble(b.cut())
    assert asm3.padded_to == 4 and len(asm3.instances) == 4
    # pad lane repeats the last real request
    assert asm3.instances[3] is asm3.instances[2]
    assert asm3.seeds[3] == asm3.seeds[2]
    assert len(asm3.inits) == 4
    np.testing.assert_array_equal(asm3.inits[3][0].assign,
                                  asm3.inits[2][0].assign)

    q.submit(INST, BUDGET, seed=9)
    asm1 = eng.assemble(b.cut(device_idle=True))
    assert asm1.padded_to == 4
    # pinned widths: every cut of one signature lands on one compiled launch
    assert asm1.batch.bucket_key == asm3.batch.bucket_key


def test_assemble_budget_seeds_match_solo_inits():
    from repro.core.api import multiwalk_inits

    clk = FakeClock()
    q = RequestQueue(clock=clk)
    b = Batcher(q, BatchPolicy(max_batch=2, max_wait=0.0))
    eng = Engine(EngineConfig(backend="numpy"), params=TSParams())
    q.submit(INST, BUDGET, seed=7, walks=3)
    asm = eng.assemble(b.cut(device_idle=True))
    assert asm.seeds == [7] and asm.padded_to == 1 and asm.batch is None
    sols, _ = multiwalk_inits(INST, 3, 7)
    assert len(asm.inits[0]) == len(sols)
    for got, want in zip(asm.inits[0], sols):
        np.testing.assert_array_equal(got.assign, want.assign)


# --------------------------------------------------------------------------- #
# service end-to-end (numpy backend: fast, deterministic)
# --------------------------------------------------------------------------- #

NUMPY_CFG = EngineConfig(backend="numpy")
PARAMS = TSParams(top_k=4)


def run(coro):
    return asyncio.run(coro)


def test_service_numpy_parity_and_streaming():
    insts = [small_instance(s) for s in range(3)]

    async def main():
        svc = SolveService(config=NUMPY_CFG,
                           policy=BatchPolicy(max_batch=3, max_wait=0.01),
                           params=PARAMS)
        await svc.start()
        rids = [await svc.submit(inst, BUDGET, seed=10 + k, walks=2)
                for k, inst in enumerate(insts)]
        events = {}
        for rid in rids:
            events[rid] = [ev async for ev in svc.stream_incumbents(rid)]
        results = [await svc.result(rid) for rid in rids]
        metrics = svc.metrics()
        await svc.shutdown()
        return rids, events, results, metrics

    rids, events, results, metrics = run(main())
    total_events = 0
    for k, (rid, rr) in enumerate(zip(rids, results)):
        solo = solve(insts[k], "tabu_multiwalk", walks=2, budget=BUDGET,
                     seed=10 + k, params=PARAMS)
        assert rr.report.makespan == solo.makespan
        assert rr.report.history == solo.history
        np.testing.assert_array_equal(rr.report.solution.assign,
                                      solo.solution.assign)
        assert rr.metrics["rid"] == rid and rr.metrics["latency"] >= 0.0
        for ev in events[rid]:
            assert ev.best_makespan >= rr.report.makespan
        total_events += len(events[rid])
    assert total_events >= 1  # anytime incumbents actually streamed
    assert metrics["completed"] == 3 and metrics["pending"] == 0
    for key in ("submitted", "batches", "mean_batch_size", "cuts_by_reason",
                "latency_p50", "latency_p99"):
        assert key in metrics
    assert not metrics["errors"]


def test_service_shutdown_drains_queue():
    # a policy that never cuts on its own: only the drain path can finish
    never = BatchPolicy(max_batch=10**6, max_wait=1e9, eager_when_idle=False)

    async def main():
        svc = SolveService(config=NUMPY_CFG, policy=never, params=PARAMS)
        await svc.start()
        rids = [await svc.submit(INST, BUDGET, seed=s) for s in range(3)]
        await asyncio.sleep(0.05)  # nothing should have been cut yet
        assert svc.metrics()["completed"] == 0
        await svc.shutdown(drain=True)
        results = [await svc.result(rid) for rid in rids]
        return results, svc.batcher.cuts_by_reason

    results, reasons = run(main())
    assert len(results) == 3
    assert all(rr.report.makespan > 0 for rr in results)
    assert set(reasons) == {"drain"}


def test_service_shutdown_without_drain_fails_pending():
    never = BatchPolicy(max_batch=10**6, max_wait=1e9, eager_when_idle=False)

    async def main():
        svc = SolveService(config=NUMPY_CFG, policy=never, params=PARAMS)
        await svc.start()
        rid = await svc.submit(INST, BUDGET, seed=0)
        await svc.shutdown(drain=False)
        with pytest.raises(ServiceClosed):
            await svc.result(rid)
        with pytest.raises(ServiceClosed):
            await svc.submit(INST, BUDGET, seed=1)
        # dropped request streams still terminate
        return [ev async for ev in svc.stream_incumbents(rid)]

    assert run(main()) == []


def test_service_unknown_rid_raises_keyerror():
    async def main():
        svc = SolveService(config=NUMPY_CFG, params=PARAMS)
        await svc.start()
        with pytest.raises(KeyError):
            await svc.result(999)
        await svc.shutdown()

    run(main())


# --------------------------------------------------------------------------- #
# device-backend parity (compiles a launch: slow lane)
# --------------------------------------------------------------------------- #

@pytest.mark.slow
def test_service_device_parity_with_solo_solve():
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core.device_search import MEM_UPDATE_DISABLED

    inst = random_instance(0, n_tasks=40, n_data=100)
    budget = Budget(max_iters=6)
    params = TSParams(max_unimproved=10**9, time_limit=1e9, top_k=5,
                      mem_update_period=MEM_UPDATE_DISABLED)
    cfg = EngineConfig(backend="device", sync_every=8, crit_cap=32,
                       batch_sizes=(2,))

    async def main():
        svc = SolveService(config=cfg,
                           policy=BatchPolicy(max_batch=2, max_wait=0.01),
                           params=params,
                           warm=[WarmSpec(inst, 2, budget)])
        await svc.start()
        rids = [await svc.submit(inst, budget, seed=s, walks=2)
                for s in (3, 9)]
        results = [await svc.result(rid) for rid in rids]
        metrics = svc.metrics()
        await svc.shutdown()
        return results, metrics

    results, metrics = run(main())
    assert metrics["warmup"]["signatures"] == 1
    for seed, rr in zip((3, 9), results):
        solo = solve(inst, "tabu_device", walks=2, budget=budget, seed=seed,
                     params=params,
                     device={"sync_every": 8, "crit_cap": 32})
        assert rr.report.makespan == solo.makespan
        assert rr.report.history == solo.history
        np.testing.assert_array_equal(rr.report.solution.assign,
                                      solo.solution.assign)
        np.testing.assert_array_equal(rr.report.solution.mem,
                                      solo.solution.mem)


# --------------------------------------------------------------------------- #
# persistent compile cache placement
# --------------------------------------------------------------------------- #

def test_compile_cache_env_var_wins_over_a_path_given_in_code(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, compiles land there and nowhere
    else, whatever directory the caller names (a fresh process: JAX reads
    the variable as it is imported)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    import repro

    env_dir, code_dir = tmp_path / "from_env", tmp_path / "from_code"
    script = (
        "import jax\n"
        "from repro.serve import enable_compilation_cache\n"
        f"print(enable_compilation_cache({str(code_dir)!r}))\n"
        "jax.jit(lambda x: x * 3 + 1)(2.0).block_until_ready()\n")
    src = str(Path(repro.__file__).resolve().parents[1])
    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(env_dir),
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join(
               [src, os.environ.get("PYTHONPATH", "")])}
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(env_dir)
    assert any(p.name.startswith("jit_") for p in env_dir.iterdir())
    assert not code_dir.exists()


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout(
        monkeypatch):
    from pathlib import Path

    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    import repro
    from repro.serve import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    knobs = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in knobs}
    try:
        first = compile_cache.enable_compilation_cache()
        again = compile_cache.enable_compilation_cache()
        checkout = Path(repro.__file__).resolve().parents[2]
        assert first == again == str(checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
