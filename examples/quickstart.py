"""Quickstart: the workload subsystem end-to-end on CPU in under a minute.

Generates instances from every registered workload family (the paper's
Table-II recipe, tree-structured graphs, FFT/stencil DSP graphs, and
model-derived residency/pipeline MDFGs), sweeps a suite through the unified
solver API, and reports per-family makespan normalized by the
family-independent lower bound.

    PYTHONPATH=src python examples/quickstart.py

For the planner path (model MDFG -> HDATS plan -> ``jax.checkpoint``
policy) see ``examples/schedule_plan.py``.
"""
from repro import Budget, solve
from repro.instances import generate, list_families, lower_bound, save_npz, sweep

if __name__ == "__main__":
    # 1. one instance from a named family, solved through repro.solve
    inst = generate("out_tree", 7, n_tasks=63, fanout=2, depth_profile="shrink")
    rep = solve(inst, "tabu", budget=Budget(time_limit=5.0), seed=0)
    print(f"{inst.name}: makespan {rep.makespan:.1f} "
          f"(lower bound {lower_bound(inst):.1f}, {rep.iterations} iters)")

    # 2. a whole suite, grouped by shape bucket and normalized by LB
    print(f"\nregistered families: {', '.join(list_families())}")
    report = sweep("smoke", solver="tabu_multiwalk", backend="numpy",
                   budget=Budget(max_iters=30, time_limit=30.0), walks=2)
    print(f"suite '{report.suite}': {len(report.rows)} instances, "
          f"{report.buckets} shape buckets, {report.wall_time:.1f}s")
    for fam, agg in sorted(report.families.items()):
        print(f"  {fam:16s} n={agg['n']}  mean makespan/LB "
              f"{agg['mean_ratio']:.2f}")

    # 3. suites round-trip losslessly through .npz
    path = save_npz("/tmp/repro_quickstart_suite.npz",
                    [generate("fft", s, width=8) for s in range(3)])
    print(f"\nsaved 3 fft instances to {path}")
