"""The benchmark's own instance recipes, drawn from ``--seed``.

These are copies of the generators the program ships
(``repro.instances.generators``: the shared Table-II platform recipe and
``random_layered``), and an FFT graph with its recursive-call tasks, which
the program's ``fft`` leaves out; they are kept here so that a change to the program
cannot move the yardstick.  A :class:`Case` holds one instance as plain
numpy arrays; :func:`to_program` hands it to the system under test as the
program's own ``Instance`` type, and the reference (``bench.reference``)
and the lower bound (``bench.bounds``) read the :class:`Case` itself.

Every instance of a configuration shares one *shape class* (task and
block buckets, in-degree widths, padded edge counts), stated in the
configuration's file.  Draws outside it are skipped, so every seed gives
the same set of launch shapes and the served program compiles once.
"""
from __future__ import annotations

import dataclasses

import numpy as np

__all__ = ["Case", "draw_pool", "shape_class", "to_program", "seed_words"]

MAX_DRAWS_PER_CASE = 64


@dataclasses.dataclass(frozen=True)
class Case:
    """One HDATS instance (paper §III) as plain arrays.

    CSR pairs: ``cons`` maps a block to its consumer tasks, ``in``/``out``
    map a task to its input/output blocks.  ``producer[d]`` is -1 for an
    initial input.  ``proc_time`` is inf on an incompatible core;
    ``mem_cap`` is inf on the unbounded slow tier.
    """

    n_tasks: int
    n_data: int
    task_edges: np.ndarray
    producer: np.ndarray
    cons_indptr: np.ndarray
    cons_idx: np.ndarray
    in_indptr: np.ndarray
    in_idx: np.ndarray
    out_indptr: np.ndarray
    out_idx: np.ndarray
    proc_time: np.ndarray
    data_size: np.ndarray
    mem_cap: np.ndarray
    access_time: np.ndarray
    mem_level: np.ndarray
    data_mem_ok: np.ndarray
    name: str

    @property
    def n_procs(self) -> int:
        return self.proc_time.shape[1]

    @property
    def n_mems(self) -> int:
        return len(self.mem_cap)

    def precedence(self) -> np.ndarray:
        """(m, 2) distinct task->task edges: direct edges plus every
        producer->consumer pair, self-loops dropped."""
        cons_owner = np.repeat(np.arange(self.n_data), np.diff(self.cons_indptr))
        prod = self.producer[cons_owner]
        pairs = np.concatenate([
            np.asarray(self.task_edges, np.int64).reshape(-1, 2),
            np.stack([prod, self.cons_idx], axis=1)[prod >= 0]])
        pairs = pairs[pairs[:, 0] != pairs[:, 1]]
        return np.unique(pairs, axis=0) if len(pairs) else pairs


def seed_words(seed: int) -> list:
    """Entropy words for ``numpy.random.SeedSequence`` from any integer."""
    return [0 if seed >= 0 else 1, abs(int(seed))]


# --------------------------------------------------------------------------- #
# recipes                                                                      #
# --------------------------------------------------------------------------- #
def _csr(n_src: int, pairs: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    if len(pairs) == 0:
        return np.zeros(n_src + 1, dtype=np.int64), np.zeros(0, dtype=np.int64)
    pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    indptr = np.zeros(n_src + 1, dtype=np.int64)
    np.cumsum(np.bincount(pairs[:, 0], minlength=n_src), out=indptr[1:])
    return indptr, pairs[:, 1].astype(np.int64)


def _platform(rng, *, n_tasks, n_data, task_edges, producer, cons_pairs,
              out_pairs, data_size, name, n_fast_cores, n_slow_cores,
              tin_tproc_tout, access_ratio, fast_mem_fraction, n_fast_tiers,
              slow_core_factor, core_restrict_prob, ddr_only_prob) -> Case:
    """Table II platform: cores, tiers and access times around a graph."""
    n_procs = n_fast_cores + n_slow_cores
    cons_arr = np.asarray(cons_pairs, dtype=np.int64).reshape(-1, 2)
    out_arr = np.asarray(out_pairs, dtype=np.int64).reshape(-1, 2)
    cons_indptr, cons_idx = _csr(n_data, cons_arr)
    in_indptr, in_idx = _csr(n_tasks, cons_arr[:, ::-1])
    out_indptr, out_idx = _csr(n_tasks, out_arr)

    tin, tproc, _ = tin_tproc_tout
    base_proc = rng.uniform(0.5 * tproc, 1.5 * tproc, size=n_tasks)
    speed = np.concatenate([
        np.ones(n_fast_cores),
        rng.uniform(slow_core_factor[0], slow_core_factor[1],
                    size=n_slow_cores)])
    jitter = rng.uniform(0.9, 1.1, size=(n_tasks, n_procs))
    proc_time = base_proc[:, None] * speed[None, :] * jitter
    restricted = rng.random(n_tasks) < core_restrict_prob
    proc_time[restricted, n_fast_cores:] = np.inf

    total_vol = float(data_size.sum())
    n_mems = n_fast_tiers + 1
    mem_cap = np.empty(n_mems)
    mem_cap[:n_fast_tiers] = fast_mem_fraction / max(1, n_fast_tiers) * total_vol
    mem_cap[-1] = np.inf

    mean_inputs = max(1e-9, len(cons_arr) / n_tasks)
    at_fast = tin / (mean_inputs * float(data_size.mean()))
    access_time = np.empty((n_procs, n_mems))
    access_time[:, :n_fast_tiers] = at_fast
    access_time[:, -1] = at_fast * access_ratio
    access_time *= rng.uniform(0.95, 1.05, size=access_time.shape)

    data_mem_ok = np.ones((n_data, n_mems), dtype=bool)
    data_mem_ok[rng.random(n_data) < ddr_only_prob, :n_fast_tiers] = False

    return Case(
        n_tasks=n_tasks, n_data=n_data,
        task_edges=np.asarray(task_edges, dtype=np.int64).reshape(-1, 2),
        producer=np.asarray(producer, dtype=np.int64),
        cons_indptr=cons_indptr, cons_idx=cons_idx,
        in_indptr=in_indptr, in_idx=in_idx,
        out_indptr=out_indptr, out_idx=out_idx,
        proc_time=proc_time, data_size=data_size.astype(np.float64),
        mem_cap=mem_cap, access_time=access_time,
        mem_level=np.arange(n_mems), data_mem_ok=data_mem_ok, name=name)


def _sizes(rng, n: int, size_range) -> np.ndarray:
    return rng.integers(size_range[0], size_range[1] + 1,
                        size=n).astype(np.float64)


def random_layered(rng, *, n_tasks, n_data, edges_per_task, data_size_range,
                   platform, name="layered") -> Case:
    """arXiv:2206.05268 Table II: blocks carry most dependencies (1-3
    later consumers each, ~5% initial inputs), direct task edges fill up
    to ``edges_per_task`` x tasks."""
    target_edges = int(edges_per_task * n_tasks)
    n_initial = max(1, n_data // 20)
    producer = np.full(n_data, -1, dtype=np.int64)
    producer[n_initial:] = rng.integers(0, max(1, n_tasks - 1),
                                        size=n_data - n_initial)
    out_pairs = np.stack([producer[n_initial:],
                          np.arange(n_initial, n_data)], axis=1)
    n_cons = rng.integers(1, 4, size=n_data)
    lo = np.where(producer < 0, 0, producer + 1)
    cand = lo[:, None] + (rng.random((n_data, 3))
                          * (n_tasks - lo)[:, None]).astype(np.int64)
    cand = np.minimum(cand, n_tasks - 1)
    live = np.arange(3)[None, :] < n_cons[:, None]
    d_of = np.broadcast_to(np.arange(n_data)[:, None], cand.shape)
    flat = np.unique(d_of[live] * n_tasks + cand[live])
    cons_pairs = np.stack([flat // n_tasks, flat % n_tasks], axis=1)

    n_task_edges = max(0, target_edges - len(cons_pairs) - len(out_pairs))
    a = rng.integers(0, n_tasks - 1, size=n_task_edges)
    b = a + 1 + (rng.random(n_task_edges) * (n_tasks - a - 1)).astype(np.int64)
    task_edges = np.stack([a, np.minimum(b, n_tasks - 1)], axis=1)
    data_size = _sizes(rng, n_data, data_size_range)
    return _platform(rng, n_tasks=n_tasks, n_data=n_data,
                     task_edges=task_edges, producer=producer,
                     cons_pairs=cons_pairs, out_pairs=out_pairs,
                     data_size=data_size, name=name, **platform)


def fft(rng, *, width, stages, data_size_range, platform, name="fft") -> Case:
    """FFT task graph (Topcuoglu, Hariri and Wu 2002) at ``width`` points:
    ``2 width - 1`` recursive-call tasks, a binary tree in heap order whose
    ``width`` leaves are the input points, above ``stages`` levels of
    ``width`` butterfly tasks.  Every task but the last level's writes one
    block: a tree task's is read by its two children, a leaf's by butterfly
    tasks ``(0, i)`` and ``(0, i XOR 1)``, and butterfly ``(l, i)``'s by
    ``(l+1, i)`` and ``(l+1, i XOR 2^(l+1))``.  The root reads the one
    initial input."""
    if width < 2 or width & (width - 1) or not 1 <= stages <= width.bit_length() - 1:
        raise ValueError(f"fft needs a power-of-2 width and 1..log2(width) "
                         f"stages, got width={width} stages={stages}")
    n_tree = 2 * width - 1
    cols = np.arange(width)
    inner = np.arange(width - 1)
    leaves = width - 1 + cols

    def fly(lvl, i):
        return n_tree + lvl * width + i

    writers = np.concatenate([np.arange(n_tree)]
                             + [fly(lvl, cols) for lvl in range(stages - 1)])
    block = {int(t): 1 + k for k, t in enumerate(writers)}   # block 0: input

    def reads(src, dst):
        return np.stack([[block[int(t)] for t in src], dst], axis=1)

    cons = [np.array([[0, 0]]),
            reads(inner, 2 * inner + 1), reads(inner, 2 * inner + 2),
            reads(leaves, fly(0, cols)), reads(leaves, fly(0, cols ^ 1))]
    for lvl in range(stages - 1):
        cons += [reads(fly(lvl, cols), fly(lvl + 1, cols)),
                 reads(fly(lvl, cols), fly(lvl + 1, cols ^ (2 << lvl)))]
    n_data = 1 + len(writers)
    out_pairs = np.stack([writers, 1 + np.arange(len(writers))], axis=1)
    producer = np.concatenate([[-1], writers]).astype(np.int64)
    data_size = _sizes(rng, n_data, data_size_range)
    return _platform(rng, n_tasks=n_tree + stages * width, n_data=n_data,
                     task_edges=np.zeros((0, 2), np.int64), producer=producer,
                     cons_pairs=np.concatenate(cons), out_pairs=out_pairs,
                     data_size=data_size, name=name, **platform)


RECIPES = {"random_layered": random_layered, "fft": fft}


# --------------------------------------------------------------------------- #
# shape class and pool                                                         #
# --------------------------------------------------------------------------- #
def _quantum(n: int, q: int) -> int:
    return max(q, q * -(-int(n) // q))


def _width(indptr: np.ndarray) -> int:
    deg = np.diff(indptr)
    w = max(1, int(deg.max()) if len(deg) else 1)
    return max(8, 1 << (w - 1).bit_length())


def shape_class(case: Case) -> list:
    """``[task bucket, cores, block bucket, tiers, [pred, succ, in, out]
    in-degree widths, [in, out] padded edge counts]``: 32-quanta buckets,
    power-of-2 widths floored at 8, 128-quanta edge pads."""
    prec = case.precedence()
    pred_indptr, _ = _csr(case.n_tasks, prec[:, ::-1] if len(prec) else prec)
    succ_indptr, _ = _csr(case.n_tasks, prec)
    return [_quantum(case.n_tasks, 32), case.n_procs,
            _quantum(case.n_data, 32), case.n_mems,
            [_width(pred_indptr), _width(succ_indptr),
             _width(case.in_indptr), _width(case.out_indptr)],
            [_quantum(len(case.in_idx), 128), _quantum(len(case.out_idx), 128)]]


def _recipe_kwargs(config: dict) -> dict:
    keys = {"random_layered": ("n_tasks", "n_data", "edges_per_task"),
            "fft": ("width", "stages")}[config["family"]]
    kw = {k: config[k] for k in keys}
    kw["data_size_range"] = tuple(config["data_size_range"])
    plat = dict(config["platform"])
    for k in ("tin_tproc_tout", "slow_core_factor"):
        plat[k] = tuple(plat[k])
    kw["platform"] = plat
    return kw


def draw_pool(config: dict, seed: int, n: int) -> list:
    """The first ``n`` draws from ``seed`` that fall in the configuration's
    shape class, in draw order."""
    recipe = RECIPES[config["family"]]
    kw = _recipe_kwargs(config)
    want = config["shape_class"]
    root = np.random.SeedSequence(seed_words(seed))
    cases, k = [], 0
    while len(cases) < n:
        if k >= MAX_DRAWS_PER_CASE * n:
            raise RuntimeError(f"{config['name']}: fewer than {n} of {k} "
                               f"draws fall in shape class {want}")
        rng = np.random.default_rng(np.random.SeedSequence(
            root.entropy, spawn_key=(k,)))
        case = recipe(rng, name=f"{config['name']}[{seed}:{k}]", **kw)
        k += 1
        if shape_class(case) == want:
            cases.append(case)
    return cases


def to_program(case: Case):
    """The system under test's ``Instance`` for ``case`` (copies arrays)."""
    from repro.core.mdfg import Instance

    fields = {f.name: getattr(case, f.name) for f in dataclasses.fields(case)}
    return Instance(**{k: v.copy() if isinstance(v, np.ndarray) else v
                       for k, v in fields.items()})
