"""A whole run of the benchmark on the CPU at rehearsal size, with the
timed path sound and then broken underneath.

``--rehearsal`` skips the harness's look for a TPU and drives everything
else: the pool, ``SolveService`` on the device backend, the closed-loop
window, the comparison and the result line.  Each fault below that can
make a served answer wrong must turn ``correct`` false."""
import json
import shutil
import subprocess
import sys

import pytest

from bench import run as bench_run
from bench.spec import ROOT

CELL = "layered250_roomy-c8-t10"


@pytest.fixture
def rehearse(monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(ROOT / ".bench_cache" / "jax"))

    def go(seed=7, trace=0):
        assert bench_run.main(["--workload", CELL, "--seed", str(seed),
                               "--seconds", "2", "--trace", str(trace),
                               "--rehearsal"]) == 0
        out = capsys.readouterr().out.strip().splitlines()
        return json.loads(out[-1])
    return go


def test_sound_rehearsal_is_correct_and_labelled(rehearse):
    line = rehearse(seed=2**31 + 5)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearsal"] is True and "metrics" not in line
    assert line["device"]["platform"] == "cpu"
    assert set(line["readings"]) == {"search_iters_per_s", "latency_p95_s",
                                     "mk_over_lb", "setup_s"}
    assert list(line)[-1] == "check"
    assert line["check"]["makespan_gap"]["value"] <= line["check"]["makespan_gap"]["limit"]


def test_traced_rehearsal_reads_the_layer_metrics(rehearse):
    line = rehearse(trace=1)
    assert line["correct"] is True
    # the CPU has no device plane: the idle share is left out, not 0
    assert set(line["readings"]) == {"queue_wait_s", "assemble_s",
                                     "iters_per_request"}


def test_an_answer_altered_where_it_is_produced_fails(rehearse, monkeypatch):
    from repro.core import device_search

    solve = device_search.solve_instances

    def altered(*a, **kw):
        res = solve(*a, **kw)
        for r in res:
            r.best_makespan *= 1 - 1e-3
        return res
    monkeypatch.setattr(device_search, "solve_instances", altered)
    line = rehearse()
    assert line["correct"] is False
    assert line["check"]["violations"]["value"] > 0


def test_half_of_each_batch_left_out_fails(rehearse, monkeypatch):
    from repro.serve.engine import Engine

    execute = Engine.execute

    def half(self, assembled, callbacks=None):
        out = execute(self, assembled, callbacks)
        return out[: max(1, len(out) // 2)]
    monkeypatch.setattr(Engine, "execute", half)
    line = rehearse()
    assert line["correct"] is False
    assert line["check"]["missing"]["value"] > 0 and line["failed"] > 0


@pytest.mark.parametrize("counts", [False, True])
def test_a_step_that_returns_its_state_unchanged_fails(rehearse, monkeypatch, counts):
    """Every answer is then its certified start schedule.  The launch
    either freezes its iteration counter too, or counts iterations that
    commit no move; either way ``unimproved`` reads 1."""
    from repro.core import device_search

    get = device_search._get_launch

    def frozen(*a, **kw):
        fn, fresh = get(*a, **kw)
        if counts:
            return (lambda ia, st, series: ({**st, "it": st["it"] + 1}, series)), fresh
        return (lambda ia, st, series: (st, series)), fresh
    monkeypatch.setattr(device_search, "_get_launch", frozen)
    line = rehearse()
    assert line["correct"] is False
    assert line["check"]["unimproved"]["value"] == 1.0
    assert line["check"]["violations"]["value"] == 0
    if counts:
        assert line["readings"]["search_iters_per_s"]["value"] > 0


def test_the_measuring_path_refuses_the_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        bench_run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                        "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_print_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu", "HOME": str(tmp_path)}
    for extra in ([], ["--rehearsal"]):
        p = subprocess.run([sys.executable, "-m", "bench.run", "--workload", CELL,
                            "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
                           cwd=tmp_path, env=env, capture_output=True, text=True,
                           timeout=300)
        assert p.returncode != 0 and p.stdout == ""
