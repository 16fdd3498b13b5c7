"""What one run leaves for the metric readers: the jobs it served with
their host times, the router's counters at the close of the window, and
the reduced trace of a traced window.  Readers in ``metrics/`` take a
:class:`RunRecord` and return a number, or ``None`` where the run has
nothing for them to read."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class RunRecord:
    cell: dict  # the cell's workload file, with "name"
    model_config: dict  # the configuration's ``model_config``, as run
    batch: int
    peaks: dict
    setup_s: float
    window: tuple  # host (start, end) of the measured window
    jobs: list  # engine Job records, the traced window's included
    router_stats: dict  # at the close of the measured window
    prefill_modules: set = dataclasses.field(default_factory=set)
    decode_modules: set = dataclasses.field(default_factory=set)
    traced: Optional[tuple] = None  # host (start, end) of the traced window
    trace: Optional[dict] = None  # chipbench.trace.reduce() of it

    def _inside(self, t0: float, t1: float, win: tuple) -> bool:
        return t0 >= win[0] and t1 <= win[1]

    def window_chunks(self, win: Optional[tuple] = None) -> list:
        """(job, chunk) for every decode chunk run inside ``win`` (default:
        the measured window)."""
        win = win or self.window
        return [(j, c) for j in self.jobs for c in j.chunks if self._inside(c.t0, c.t1, win)]

    def window_prefills(self, win: Optional[tuple] = None) -> list:
        """Jobs whose first token reached the host inside ``win``."""
        win = win or self.window
        return [j for j in self.jobs if self._inside(j.t_start, j.t_first, win)]


def quantile(values, weights, q: float) -> float:
    """The ``q`` quantile of ``values`` each counted ``weights`` times: the
    least value whose cumulative weight reaches ``q`` of the total."""
    v = np.asarray(values, np.float64)
    w = np.asarray(weights, np.float64)
    order = np.argsort(v, kind="stable")
    cum = np.cumsum(w[order])
    i = int(np.searchsorted(cum, q * cum[-1], side="left"))
    return float(v[order][min(i, v.size - 1)])
