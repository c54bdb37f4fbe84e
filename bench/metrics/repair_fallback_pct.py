"""Search driver: percent of the walks whose device best was over capacity
that were served their best feasible schedule, because Algorithm 3's
repair of that best came out worse (``repro.core.device_search.REPAIRS``:
``fallback`` over ``infeasible``).  The service runs in the benchmark's
process, so this reads the counter's whole-run ratio, warm-up included.
None where the program has no such counter or no walk best was over
capacity."""


def pct(counter) -> "float | None":
    return 100.0 * counter["fallback"] / counter["infeasible"] \
        if counter.get("infeasible") else None


def read(run):
    try:
        from repro.core.device_search import REPAIRS
    except ImportError:
        return None
    return pct(REPAIRS)
