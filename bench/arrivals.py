"""The one traffic generator: a mix file under ``bench/traffic/`` in,
a seeded workload out.

A mix file names the entry it drives and its loop:

* ``"loop": "open"`` — requests due on a clock, sent whether or not earlier
  ones finished.  ``rate_per_s`` sets the mean rate; ``rows`` the rows of
  every request.  Due times are the order statistics of uniform draws over
  the window: a Poisson process conditioned on its count.
* ``"loop": "closed"`` — ``clients`` requests outstanding, each sent again
  as soon as its answer is in; inputs come from a pool of
  ``pool_requests`` requests.
* ``"loop": "back_to_back"`` — ``batch_rows``-row batches from a ring of
  ``ring`` batches, dispatched with at most ``inflight`` outstanding.

Every seed gets the same multiset of inter-arrival gaps (drawn from the
mix's own ``shape_seed``); the run's seed only permutes them and draws the
input rows, so runs with different seeds do the same amount of work.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """The generator of one use (``stream``) of a run's seed; any whole
    number is a seed, 64 bits and more included."""
    return np.random.default_rng([int(seed) % 2**64, stream])


@dataclasses.dataclass
class OpenSchedule:
    """An open loop's requests, in due order."""

    due_s: np.ndarray      # [n] seconds from the window's start, sorted
    rows: np.ndarray       # [n] rows per request
    starts: np.ndarray     # [n] first row of each request in the input rows
    keep: np.ndarray       # [n] bool: answer kept for the correctness check

    @property
    def total_rows(self) -> int:
        """Rows over all requests."""
        return int(self.rows.sum())


def open_schedule(mix: dict, seconds: float, seed: int,
                  rate_per_s: Optional[float] = None) -> OpenSchedule:
    """The open loop's requests over a ``seconds`` window.

    The inter-arrival gaps come from the mix's ``shape_seed``; ``seed``
    permutes them, and picks which answers are kept for the check
    (``check_share``)."""
    rate = float(rate_per_s if rate_per_s is not None else mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    shape = np.random.default_rng(int(mix["shape_seed"]))
    gaps = np.diff(np.sort(shape.uniform(0.0, seconds, n)), prepend=0.0)
    sizes = np.full(n, int(mix["rows"]), np.int64)
    run = rng(seed, 1)
    due = np.cumsum(gaps[run.permutation(n)])
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    keep = run.random(n) < float(mix.get("check_share", 1.0))
    return OpenSchedule(due_s=due, rows=sizes, starts=starts, keep=keep)


def input_rows(cfg: dict, n: int, seed: int) -> np.ndarray:
    """``n`` float32 input rows over the configuration's ``input_range``,
    from the seed (host side: the fleet receives host rows)."""
    lo, hi = cfg["input_range"]
    return rng(seed, 2).uniform(lo, hi, (n, int(cfg["in_features"]))).astype(
        np.float32)


def closed_order(mix: dict, n: int, seed: int) -> np.ndarray:
    """Which pool request each of the first ``n`` sends reuses."""
    return rng(seed, 3).integers(0, int(mix["pool_requests"]), n)


def keep_mask(share: float, n: int, seed: int) -> np.ndarray:
    """Which of ``n`` requests or batches keep their answer for the
    check, drawn from the seed."""
    return rng(seed, 4).random(n) < share
