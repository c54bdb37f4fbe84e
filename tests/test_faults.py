"""Fault tolerance (DESIGN.md §13): failure taxonomy, deterministic
injection, checkpoint save/load, and crash/resume bit-parity of the
device multiwalk engine."""
import asyncio
import dataclasses

import numpy as np
import pytest

from repro.analysis.sanitize import SanitizeError
from repro.core import Budget, TSParams, random_instance
from repro.core.mdfg import InfeasibleInstanceError
from repro.faults import checkpoint as fckpt
from repro.faults import inject as finj
from repro.faults.errors import (
    CertifyFailure,
    CompileTimeout,
    DeviceLost,
    EngineCrashed,
    InfeasibleRequest,
    LaunchFailure,
    QueueOverload,
    ReproError,
    wrap_error,
)


# --------------------------------------------------------------------------- #
# taxonomy                                                                    #
# --------------------------------------------------------------------------- #
def test_retryability_encoded_on_the_class():
    assert CompileTimeout.retryable
    assert LaunchFailure.retryable
    assert DeviceLost.retryable
    assert CertifyFailure.retryable
    assert not InfeasibleRequest.retryable
    assert not QueueOverload.retryable
    assert not EngineCrashed.retryable
    assert not ReproError.retryable


def test_errors_carry_rid_and_injected():
    e = LaunchFailure("boom", rid=7, injected=True)
    assert e.rid == 7 and e.injected and isinstance(e, ReproError)
    assert QueueOverload("full", retry_after=0.25).retry_after == 0.25


def test_wrap_error_passthrough_adopts_rid():
    e = DeviceLost("gone")
    w = wrap_error(e, rid=3)
    assert w is e and w.rid == 3
    # an already-attributed error keeps its rid
    assert wrap_error(DeviceLost("gone", rid=1), rid=9).rid == 1


def test_wrap_error_maps_known_causes():
    cert = wrap_error(SanitizeError("bad certificate", None), rid=2)
    assert isinstance(cert, CertifyFailure) and cert.rid == 2
    assert isinstance(cert.__cause__, SanitizeError)

    infeas = wrap_error(
        InfeasibleInstanceError("no fit", block=0, task=-1), rid=4)
    assert isinstance(infeas, InfeasibleRequest) and not infeas.retryable

    other = wrap_error(ValueError("xla fell over"), rid=5)
    assert isinstance(other, LaunchFailure)
    assert isinstance(other.__cause__, ValueError)


# --------------------------------------------------------------------------- #
# deterministic injection                                                     #
# --------------------------------------------------------------------------- #
def test_helpers_are_noops_without_a_plan():
    with finj.plan_context(None):
        finj.fire("engine.execute.launch", key=1)  # must not raise
        arr = np.arange(5)
        assert finj.corrupt("engine.result.incumbent", arr, key=1) is arr
        assert finj.nan_value("engine.result.makespan", 3.5, key=1) == 3.5
        assert finj.skewed("service.clock", 10.0, key=1) == 10.0
        # unregistered points are not even checked on the fast path
        finj.fire("not.registered", key=1)


def test_decisions_are_pure_and_order_independent():
    plan = finj.FaultPlan(seed=11, rate=0.5)
    keys = list(range(40))
    first = [finj.would_fire(plan, "fire", "engine.execute.launch", k)
             for k in keys]
    second = [finj.would_fire(plan, "fire", "engine.execute.launch", k)
              for k in reversed(keys)][::-1]
    assert first == second
    assert any(first) and not all(first)  # rate 0.5 fires some, not all
    # a different seed reshuffles the schedule
    other = [finj.would_fire(finj.FaultPlan(seed=12, rate=0.5), "fire",
                             "engine.execute.launch", k) for k in keys]
    assert other != first


def test_fire_matches_would_fire_prediction():
    plan = finj.FaultPlan(seed=3, rate=0.6,
                          kinds=("launch_error", "device_lost"))
    with finj.plan_context(plan):
        for k in range(30):
            kind = finj.would_fire(plan, "fire", "engine.execute.launch", k)
            if kind is None:
                finj.fire("engine.execute.launch", key=k)
            else:
                cls = (LaunchFailure if kind == "launch_error"
                       else DeviceLost)
                with pytest.raises(cls) as ei:
                    finj.fire("engine.execute.launch", key=k, rid=k)
                assert ei.value.injected and ei.value.rid == k


def test_rate_zero_plan_never_fires_and_rate_one_always():
    zero = finj.FaultPlan(seed=0, rate=0.0)
    one = finj.FaultPlan(seed=0, rate=1.0, kinds=("launch_error",))
    for k in range(20):
        assert finj.would_fire(zero, "fire", "engine.execute.launch", k) \
            is None
        assert finj.would_fire(one, "fire", "engine.execute.launch", k) \
            == "launch_error"


def test_corrupt_copies_never_mutates():
    plan = finj.FaultPlan(seed=0, rate=1.0, kinds=("corrupt_incumbent",))
    with finj.plan_context(plan):
        ints = np.arange(6)
        out = finj.corrupt("engine.result.incumbent", ints, key=2)
        assert out is not ints
        assert np.array_equal(ints, np.arange(6))  # input untouched
        assert (out != ints).sum() == 1            # exactly one entry flipped

        floats = np.ones(4)
        fout = finj.corrupt("engine.result.incumbent", floats, key=2)
        assert np.isnan(fout).sum() == 1

    # each helper fires only when its kind is in the plan
    with finj.plan_context(finj.FaultPlan(seed=0, rate=1.0,
                                          kinds=("nan_duration",))):
        assert np.isnan(finj.nan_value("engine.result.makespan", 1.0, key=2))
        arr = np.arange(3)
        assert finj.corrupt("engine.result.incumbent", arr, key=2) is arr
    skew_plan = finj.FaultPlan(seed=0, rate=1.0, kinds=("clock_skew",))
    with finj.plan_context(skew_plan):
        assert finj.skewed("service.clock", 10.0, key=2) \
            == 10.0 + skew_plan.skew_seconds


def test_active_plan_rejects_unregistered_point():
    with finj.plan_context(finj.FaultPlan(rate=1.0)):
        with pytest.raises(ValueError, match="unregistered injection point"):
            finj.fire("engine.execute.lunch", key=0)


def test_registry_covers_the_documented_points():
    assert {"engine.warmup.compile", "engine.execute.launch",
            "engine.result.incumbent", "engine.result.makespan",
            "service.clock", "device_search.sync"} \
        <= finj.registered_points()


@pytest.mark.parametrize("raw", ["", "0", "false", "no", "off"])
def test_env_off_values(monkeypatch, raw):
    monkeypatch.setenv("REPRO_FAULTS", raw)
    assert finj.plan_from_env() is None


def test_env_parsing(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "1")
    assert finj.plan_from_env() == finj.FaultPlan()
    monkeypatch.setenv(
        "REPRO_FAULTS",
        "seed=7, rate=0.25, kinds=launch_error+clock_skew, "
        "points=service.clock, skew_seconds=0.5")
    plan = finj.plan_from_env()
    assert plan == finj.FaultPlan(seed=7, rate=0.25,
                                  kinds=("launch_error", "clock_skew"),
                                  points=("service.clock",),
                                  skew_seconds=0.5)
    monkeypatch.setenv("REPRO_FAULTS", "bogus=1")
    with pytest.raises(ValueError, match="unknown key"):
        finj.plan_from_env()


# --------------------------------------------------------------------------- #
# checkpoint container                                                        #
# --------------------------------------------------------------------------- #
def _toy_checkpoint() -> fckpt.SearchCheckpoint:
    return fckpt.snapshot(
        instance_fp=123, params_fp=456, walks=2, sync_index=3, crit_cap=16,
        elapsed=1.25, n_exact_host=9, g_best=41.5, init_mk_min=60.0,
        g_hist=[(0, 60.0), (12, 41.5)],
        histories=[[(0, 60.0)], [(4, 50.0), (12, 41.5)]],
        state={"best_mk": np.array([41.5, 50.0]),
               "assign": np.arange(8).reshape(2, 4),
               "key": np.array([1, 2], dtype=np.uint32)},
        feasible={"mk": np.array([44.0, 52.5]),
                  "assign": np.arange(8, 16).reshape(2, 4)})


def test_checkpoint_save_load_roundtrip(tmp_path):
    ck = _toy_checkpoint()
    path = fckpt.save(ck, str(tmp_path / "sub" / "state.npz"))
    back = fckpt.load(path)
    for f in ("version", "instance_fp", "params_fp", "walks", "sync_index",
              "crit_cap", "elapsed", "n_exact_host", "g_best",
              "init_mk_min", "g_hist", "histories"):
        assert getattr(back, f) == getattr(ck, f), f
    assert set(back.state) == set(ck.state)
    for k in ck.state:
        assert np.array_equal(back.state[k], ck.state[k])
        assert back.state[k].dtype == np.asarray(ck.state[k]).dtype
    assert set(back.feasible) == set(ck.feasible)
    for k in ck.feasible:
        assert np.array_equal(back.feasible[k], ck.feasible[k])


def test_checkpoint_snapshot_is_deep():
    state = {"mk": np.array([5.0])}
    ck = fckpt.snapshot(
        instance_fp=1, params_fp=2, walks=1, sync_index=0, crit_cap=8,
        elapsed=0.0, n_exact_host=0, g_best=5.0, init_mk_min=5.0,
        g_hist=[], histories=[[]], state=state,
        feasible={"mk": np.array([5.0])})
    state["mk"][0] = -1.0
    assert ck.state["mk"][0] == 5.0


def test_check_compatible_rejects_mismatches():
    ck = _toy_checkpoint()
    fckpt.check_compatible(ck, instance_fp=123, params_fp=456, walks=2)
    for kw in ({"instance_fp": 99}, {"params_fp": 99}, {"walks": 3}):
        args = {"instance_fp": 123, "params_fp": 456, "walks": 2, **kw}
        with pytest.raises(fckpt.CheckpointMismatch):
            fckpt.check_compatible(ck, **args)


# --------------------------------------------------------------------------- #
# crash/resume bit-parity (device engine)                                     #
# --------------------------------------------------------------------------- #
def _resume_roundtrip(walks: int, tmp_path, mem_update_period=None):
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core.device_search import (
        MEM_UPDATE_DISABLED,
        DeviceConfig,
        device_multiwalk,
    )
    from repro.core.greedy import STRATEGIES, construct_greedy

    inst = random_instance(0, n_tasks=40, n_data=100)
    # iteration-bound only, so the run spans several sync boundaries
    params = TSParams(seed=3, max_unimproved=10**9, time_limit=1e9, top_k=5,
                      max_iters=40,
                      mem_update_period=mem_update_period or MEM_UPDATE_DISABLED)
    cfg = DeviceConfig(sync_every=16, crit_cap=32)
    inits = [construct_greedy(inst, STRATEGIES[w % len(STRATEGIES)], rng=3 + w)
             for w in range(walks)]

    ref_ckpts = []
    ref = device_multiwalk(inst, [s.copy() for s in inits], params,
                           config=cfg, on_checkpoint=ref_ckpts.append)
    assert len(ref_ckpts) >= 2, "need a mid-run sync to resume from"

    # crash mid-run: deterministic device_lost at sync 1 (after checkpoint)
    plan = finj.FaultPlan(seed=0, rate=1.0, kinds=("device_lost",),
                          points=("device_search.sync",))
    got = []
    with finj.plan_context(plan):
        with pytest.raises(DeviceLost):
            device_multiwalk(inst, [s.copy() for s in inits], params,
                             config=cfg, on_checkpoint=got.append)
    assert len(got) == 1  # checkpoint lands before the injected crash

    path = fckpt.save(got[-1], str(tmp_path / "crash.npz"))
    resumed = device_multiwalk(inst, [s.copy() for s in inits], params,
                               config=cfg, resume_from=fckpt.load(path))

    assert resumed.best_makespan == ref.best_makespan
    assert resumed.history == ref.history
    assert resumed.iterations == ref.iterations
    assert resumed.n_exact_evals == ref.n_exact_evals
    assert resumed.n_approx_evals == ref.n_approx_evals
    assert resumed.stop_reason == ref.stop_reason
    assert np.array_equal(resumed.best.assign, ref.best.assign)
    assert np.array_equal(resumed.best.mem, ref.best.mem)
    assert resumed.best.proc_seq == ref.best.proc_seq


def test_crash_resume_bit_parity_w1(tmp_path):
    _resume_roundtrip(1, tmp_path)


def test_crash_resume_bit_parity_with_alg3_at_syncs(tmp_path):
    """With Algorithm 3 at every sync the checkpoint carries each walk's
    best feasible schedule, which the final repair may serve."""
    _resume_roundtrip(1, tmp_path, mem_update_period=1)


@pytest.mark.slow
def test_crash_resume_bit_parity_w8(tmp_path):
    _resume_roundtrip(8, tmp_path)


def test_resume_rejects_wrong_instance(tmp_path):
    jax = pytest.importorskip("jax")  # noqa: F841
    from repro.core.device_search import (
        MEM_UPDATE_DISABLED,
        DeviceConfig,
        device_multiwalk,
    )
    from repro.core.greedy import construct_greedy

    params = TSParams(seed=3, max_unimproved=15, time_limit=1e9, top_k=5,
                      max_iters=40, mem_update_period=MEM_UPDATE_DISABLED)
    cfg = DeviceConfig(sync_every=16, crit_cap=32)
    inst = random_instance(0, n_tasks=40, n_data=100)
    ckpts = []
    device_multiwalk(inst, [construct_greedy(inst, "slack_first", rng=3)],
                     params, config=cfg, on_checkpoint=ckpts.append)
    other = random_instance(1, n_tasks=40, n_data=100)
    with pytest.raises(fckpt.CheckpointMismatch):
        device_multiwalk(other,
                         [construct_greedy(other, "slack_first", rng=3)],
                         params, config=cfg, resume_from=ckpts[0])


def test_poisoning_a_signature_logs_a_warning(caplog):
    """The numpy fallback keeps serving a poisoned signature, so the switch
    must be visible in the log."""
    import logging

    from repro.serve import ResilienceController, ResiliencePolicy, RetryPolicy

    ctl = ResilienceController(ResiliencePolicy(
        retry=RetryPolicy(max_attempts=5, poison_after=2)))
    sig = ("bucket", 4)
    with caplog.at_level(logging.WARNING, logger="repro.serve.resilience"):
        for attempt in (1, 2):
            assert not ctl.use_fallback(sig)
            ctl.on_failure(rid=7, signature=sig, attempts=attempt,
                           exc=LaunchFailure("launch lost", rid=7), now=0.0)
    assert ctl.use_fallback(sig)
    poisoned = [r for r in caplog.records if "poisoned" in r.getMessage()]
    assert len(poisoned) == 1 and poisoned[0].levelno == logging.WARNING


# --------------------------------------------------------------------------- #
# service integration under an active plan                                    #
# --------------------------------------------------------------------------- #
def test_service_accounts_every_request_under_faults(monkeypatch):
    """Numpy-backend service under a 4-kind plan: every submitted request
    reaches exactly one terminal state — a certified result or a typed
    ReproError — and survivors are bit-identical to solo solves."""
    from repro.core import solve
    from repro.serve import BatchPolicy, EngineConfig, SolveService

    monkeypatch.setenv("REPRO_SANITIZE", "1")
    insts = [random_instance(s, n_tasks=24, n_data=60) for s in range(8)]
    budget = Budget(max_iters=4)
    solo = [solve(inst, "tabu_multiwalk", walks=2, budget=budget, seed=i)
            for i, inst in enumerate(insts)]
    plan = finj.FaultPlan(
        seed=5, rate=0.3,
        kinds=("launch_error", "corrupt_incumbent", "nan_duration",
               "clock_skew"))

    async def run():
        svc = SolveService(
            config=EngineConfig(backend="numpy", batch_sizes=(4,)),
            policy=BatchPolicy(max_batch=4, max_wait=0.01))
        await svc.start()
        rids = [await svc.submit(inst, budget, seed=i, walks=2)
                for i, inst in enumerate(insts)]
        outs = {}
        for rid in rids:
            try:
                outs[rid] = await asyncio.wait_for(svc.result(rid),
                                                   timeout=60.0)
            except ReproError as e:
                outs[rid] = e
        await svc.shutdown()
        return rids, outs, svc.metrics()

    with finj.plan_context(plan):
        rids, outs, metrics = asyncio.run(run())

    assert len(rids) == len(set(rids)) == 8
    assert set(outs) == set(rids)
    for i, rid in enumerate(rids):
        out = outs[rid]
        if isinstance(out, ReproError):
            continue  # typed terminal failure — attributable and expected
        assert out.metrics.get("certified") is True
        assert out.report.makespan == solo[i].makespan
        assert np.array_equal(out.report.solution.assign,
                              solo[i].solution.assign)
    n_failed = sum(isinstance(o, ReproError) for o in outs.values())
    assert metrics["failed"] == n_failed
