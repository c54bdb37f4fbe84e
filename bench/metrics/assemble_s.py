"""Engine host prep: mean seconds per cut of the engine's assembly (greedy
walk inits, packing; ``RequestResult.metrics["assemble_seconds"]``), one
value per cut completed in the window."""
import math


def read(run):
    per_cut = {}
    for c in run.window.answered:
        if not math.isnan(c.assemble_s):
            per_cut[c.cut] = c.assemble_s
    return sum(per_cut.values()) / len(per_cut) if per_cut else None
