"""Window arithmetic over a run's completion timeline.

A *completion* is one answered request as the client saw it: when it was
sent and when its answer arrived, both on the client's clock.  Requests
answered by one batched launch form one *cut*; the cut completes when its
first answer arrives.

The window opens at a cut completion after warm-up and closes at the
last cut completion no later than ``seconds`` after it opened, or at the
first one after it opened where none came in time, so that a window
always holds at least one whole cut.  Every end-to-end number is taken
over the requests whose cut completed inside ``(open, close]``:

* the rate: their request-iterations over the window's length;
* the latency tail: the nearest-rank 95th percentile of their
  send-to-answer times;
* the quality: the mean of their makespan over lower bound.
"""
from __future__ import annotations

import dataclasses
import math

__all__ = ["Completion", "cuts", "close_time", "Window"]


@dataclasses.dataclass(frozen=True)
class Completion:
    rid: int
    sent: float
    done: float
    cut: object              # equal for every request of one launch
    iterations: int = 0
    mk_over_lb: float = float("nan")
    queue_wait: float = float("nan")
    assemble_s: float = float("nan")
    error: "str | None" = None

    @property
    def latency(self) -> float:
        return self.done - self.sent


def cuts(completions) -> list:
    """``[(completion time, [completions]), ...]`` in time order."""
    groups: dict = {}
    for c in completions:
        groups.setdefault(c.cut, []).append(c)
    return sorted(((min(c.done for c in g), g) for g in groups.values()),
                  key=lambda x: x[0])


def close_time(cut_times, opened: float, seconds: float) -> "float | None":
    """The window's close, or None while it cannot be decided yet (no cut
    after ``opened`` by ``opened + seconds``)."""
    after = [t for t in cut_times if t > opened]
    if not after:
        return None
    in_time = [t for t in after if t <= opened + seconds]
    return in_time[-1] if in_time else after[0]


def nearest_rank(values, q: float) -> float:
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


@dataclasses.dataclass
class Window:
    opened: float
    closed: float
    completions: list  # every request whose cut completed in (opened, closed]

    @classmethod
    def of(cls, completions, opened: float, closed: float) -> "Window":
        inside = [c for t, g in cuts(completions) if opened < t <= closed
                  for c in g]
        return cls(opened, closed, inside)

    @property
    def seconds(self) -> float:
        return self.closed - self.opened

    @property
    def answered(self) -> list:
        return [c for c in self.completions if c.error is None]

    def n_cuts(self) -> int:
        return len({c.cut for c in self.completions})

    def search_iters_per_s(self) -> float:
        return sum(c.iterations for c in self.answered) / self.seconds

    def latency_p95_s(self) -> float:
        return nearest_rank([c.latency for c in self.answered], 0.95)

    def mk_over_lb(self) -> float:
        a = self.answered
        return sum(c.mk_over_lb for c in a) / len(a)
