"""The one traffic generator. A mix is a data file under ``traffic/``;
the offered rate is the cell's (``cells/<cell>.json``).

Every seed gets the same work: lengths are the stratified quantiles of
their distribution and the gaps between arrivals the stratified
quantiles of the exponential, permuted, and prompt ids are drawn from
the run's seed. The permutation comes from the run's seed too, unless
the mix names a ``schedule_seed``: then every run gets the same
schedule (which request comes when, and how long it is), and the seed
changes the ids and the weights only. An open-loop mix needs that:
which requests arrive together decides how long they queue behind the
decode horizon, and TTFT's tail then moves by a factor of ten from one
order to the next (see PERF.md).

Arrival kinds:

* ``poisson``: open loop at ``rate_per_s``. Three segments, each with
  its gaps scaled to fill it exactly: ``ramp_s`` before the window
  (fills the batch, not counted), the window itself (``rate x seconds``
  requests, counted), and ``tail_s`` after it (keeps the load on while
  the counted requests finish, not counted).
* ``all_at_once``: ``n_requests`` due at t = 0 (offline batch).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Planned:
    """One request of the schedule: due time (seconds from the window's
    start), prompt ids, exact output length, and whether the window's
    metrics count it."""
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    counted: bool


def log_uniform_quantiles(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` whole numbers in [lo, hi] at the stratified quantiles of a
    log-uniform distribution."""
    u = (np.arange(n) + 0.5) / n
    v = np.exp(math.log(lo) + u * (math.log(hi + 1) - math.log(lo)))
    return np.clip(np.floor(v), lo, hi).astype(np.int64)


def exponential_gaps(n: int, length_s: float) -> np.ndarray:
    """``n`` gaps at the stratified quantiles of an exponential, scaled
    to sum to ``length_s``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (length_s / g.sum())


def _lengths(spec: Dict, n: int, rng) -> np.ndarray:
    if spec["dist"] != "log_uniform":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return rng.permutation(log_uniform_quantiles(spec["min"], spec["max"],
                                                 n))


def _segment(mix, n, start_s, length_s, counted, order, ids, vocab):
    prompts = _lengths(mix["prompt_tokens"], n, order)
    outputs = _lengths(mix["output_tokens"], n, order)
    if length_s > 0:
        gaps = order.permutation(exponential_gaps(n, length_s))
        due = start_s + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    else:
        due = np.full(n, start_s)
    return [Planned(float(due[i]),
                    ids.integers(0, vocab, int(prompts[i])).astype(np.int32),
                    int(outputs[i]), counted) for i in range(n)]


def generate(mix: Dict, cell: Dict, seed: int, seconds: float,
             vocab: int) -> List[Planned]:
    """The cell's schedule for one run, ordered by due time."""
    ids = np.random.default_rng(int(seed))
    order = (np.random.default_rng(int(mix["schedule_seed"]))
             if "schedule_seed" in mix else ids)
    kind = mix["arrival"]
    if kind == "poisson":
        rate = float(cell["rate_per_s"])
        segs = [(-mix["ramp_s"], mix["ramp_s"], False),
                (0.0, seconds, True),
                (seconds, mix["tail_s"], False)]
        out: List[Planned] = []
        for start, length, counted in segs:
            out += _segment(mix, max(1, round(rate * length)), start,
                            length, counted, order, ids, vocab)
        return out
    if kind == "all_at_once":
        return _segment(mix, int(mix["n_requests"]), 0.0, 0.0, True, order,
                        ids, vocab)
    raise ValueError(f"unknown arrival kind {kind!r}")


def prompt_support(mix: Dict) -> range:
    """Every prompt length the mix can send."""
    p = mix["prompt_tokens"]
    return range(int(p["min"]), int(p["max"]) + 1)
