"""Algorithm 3 (host): percent of the blocks Algorithm 3 placed that its
capacity probe turned away from a faster compatible tier
(``repro.core.memory_update.ALG3``: ``refused`` over ``blocks``).  The
service runs in the benchmark's process, so this reads the counter's
whole-run ratio, warm-up included.  None where the program has no such
counter or placed no block."""


def pct(counter) -> "float | None":
    return 100.0 * counter["refused"] / counter["blocks"] \
        if counter.get("blocks") else None


def read(run):
    try:
        from repro.core.memory_update import ALG3
    except ImportError:
        return None
    return pct(ALG3)
