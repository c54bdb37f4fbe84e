"""Planner bridge: residency/pipeline MDFG extraction + plan quality + lowering."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from repro.analysis.certify import certify_solution
from repro.configs.base import SHAPE_CELLS
from repro.configs.registry import ARCH_IDS, get_config
from repro.core import TSParams, exact_schedule, memory_feasible, solve
from repro.plan import (
    ACT_CLASSES,
    ResidencyPlan,
    hbm_activation_budget,
    layer_costs,
    param_state_bytes,
    pipeline_instance,
    plan_pipeline,
    plan_residency,
    plan_residency_lb,
    residency_instance,
)
from repro.plan.extract import contiguous_stage_map

TRAIN = SHAPE_CELLS[0]


def _scan_group(cfg) -> int:
    """Largest of the small scan groups that divides the layer count."""
    return next(g for g in (4, 3, 2, 1) if cfg.n_layers % g == 0)


def _assert_certified(inst, rep):
    cert = certify_solution(inst, rep.solution, reported_makespan=rep.makespan)
    assert cert.ok, f"{inst.name}: {cert.summary()}"


def test_layer_costs_scale_with_width():
    small = get_config("granite-moe-1b-a400m")
    big = get_config("qwen2.5-14b")
    cs = layer_costs(small, TRAIN)
    cb = layer_costs(big, TRAIN)
    assert sum(c.flops_fwd for c in cb) > 5 * sum(c.flops_fwd for c in cs)
    for c in cs + cb:
        assert c.flops_fwd > 0
        assert all(v >= 0 for v in c.act_bytes.values())


def test_param_state_bytes_optimizer_choice():
    cfg = get_config("llama3-405b")
    adamw_b = param_state_bytes(cfg, optimizer="adamw")
    adafactor_b = param_state_bytes(cfg, optimizer="adafactor")
    assert adafactor_b < 0.6 * adamw_b
    # 405B with full adamw cannot leave activation room on 256 chips
    assert hbm_activation_budget(cfg, optimizer="adamw") < \
        hbm_activation_budget(cfg, optimizer="adafactor")


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_layer_costs_positive(arch):
    cfg = get_config(arch)
    for c in layer_costs(cfg, TRAIN):
        assert c.flops_fwd > 0
        assert all(v >= 0 for v in c.act_bytes.values())
    assert param_state_bytes(cfg, optimizer="adamw") > 0
    assert param_state_bytes(cfg, optimizer="adafactor") > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_residency_instance_is_valid_hdats(arch):
    cfg = get_config(arch)
    inst, meta = residency_instance(cfg, TRAIN, scan_group=_scan_group(cfg))
    assert inst.n_tasks == 2 * meta["n_groups"]
    rep = solve(inst, "greedy:slack_first")
    sched = exact_schedule(inst, rep.solution)
    assert sched is not None and memory_feasible(inst, rep.solution, sched)
    # remat tier must be the most expensive per-byte access for this graph
    assert inst.access_time[0, 2] > inst.access_time[0, 0]
    _assert_certified(inst, rep)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_pipeline_instance_is_valid_hdats(arch):
    cfg = get_config(arch)
    inst, _ = pipeline_instance(cfg, TRAIN, n_stages=4, n_microbatches=6)
    assert inst.n_tasks == 2 * 4 * 6  # fwd + bwd per stage and microbatch
    rep = solve(inst, "greedy:slack_first")
    _assert_certified(inst, rep)


@pytest.mark.parametrize("arch", ["llama3-405b", "mamba2-780m", "recurrentgemma-2b"])
def test_plan_beats_or_matches_lb(arch):
    cfg = get_config(arch)
    opt = "adafactor" if arch == "llama3-405b" else "adamw"
    plan = plan_residency(cfg, TRAIN, optimizer=opt, ts_params=TSParams.fast())
    lb = plan_residency_lb(cfg, TRAIN, optimizer=opt)
    assert plan.est_step_time <= lb.est_step_time * 1.02, (
        f"TS plan worse than LB: {plan.est_step_time} vs {lb.est_step_time}"
    )
    assert plan.scan_group >= 1 and cfg.n_layers % plan.scan_group == 0


def _toy_plan(save_names, offload_names) -> ResidencyPlan:
    return ResidencyPlan(arch_id="toy", cell=TRAIN.name, scan_group=1,
                         save_names=save_names, offload_names=offload_names,
                         est_step_time=1.0, hbm_budget=1.0)


POLICY_FORMS = {
    "save_only": _toy_plan(ACT_CLASSES[:2], ()),
    "offload_and_save": _toy_plan(ACT_CLASSES[:1], ACT_CLASSES[1:3]),
    "none": _toy_plan((), ()),
}


@pytest.mark.parametrize("form", sorted(POLICY_FORMS))
def test_plan_policy_lowers_and_compiles(form):
    """Each form of a plan's checkpoint policy lowers through jax.checkpoint
    and differentiates to the same gradient as the unchecked function."""
    policy = POLICY_FORMS[form].policy()
    assert (policy is None) == (form == "none")
    ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) / 4.0
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    def loss(w):
        h = x
        for i in range(w.shape[0]):
            h = checkpoint_name(jnp.tanh(h @ w[i]), ACT_CLASSES[i])
        return (h ** 2).sum()

    val, grads = jax.jit(jax.value_and_grad(jax.checkpoint(loss, policy=policy)))(ws)
    assert bool(jnp.isfinite(val))
    ref_val, ref_grads = jax.value_and_grad(loss)(ws)
    np.testing.assert_allclose(val, ref_val, rtol=1e-6)
    np.testing.assert_allclose(grads, ref_grads, rtol=1e-5, atol=1e-6)


def test_contiguous_stage_map_balances():
    costs = np.ones(24)
    sm = contiguous_stage_map(costs, np.ones(4), 4)
    assert (np.bincount(sm) == 6).all()
    # straggler stage gets fewer layers
    sm2 = contiguous_stage_map(costs, np.array([1.0, 1.0, 2.0, 1.0]), 4)
    assert np.bincount(sm2, minlength=4)[2] < 6
    assert (np.diff(sm2) >= 0).all()


def test_pipeline_plan_schedules_all_microbatches():
    cfg = get_config("recurrentgemma-2b")
    out = plan_pipeline(cfg, TRAIN, n_stages=4, n_microbatches=6, use_tabu=False)
    assert len(out["stage_of_layer"]) == cfg.n_layers
    for s, order in enumerate(out["microbatch_order"]):
        assert sorted(set(order)) == list(range(6))
        assert len(order) == 12  # fwd + bwd per microbatch
    assert out["est_step_time"] > 0
    # heterogeneous layer kinds: rec layers cheaper than attn ⇒ stage sizes
    # need not be equal, but all layers must be assigned
    assert np.bincount(out["stage_of_layer"]).sum() == cfg.n_layers


def test_pipeline_tabu_not_worse_than_lb():
    cfg = get_config("granite-moe-1b-a400m")
    out = plan_pipeline(cfg, TRAIN, n_stages=4, n_microbatches=6,
                        ts_params=TSParams.fast())
    assert out["est_step_time"] <= out["lb_step_time"] * 1.05
