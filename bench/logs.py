"""Event logs for the benchmark, made from a seed.

The process model is the one of ``synthetic.generate`` in the program (a
Markov chain over activities: a Dirichlet start over the first three
activities, transitions on a random 30% mask plus the superdiagonal),
fixed per configuration by its ``model_seed``: the deployment mines one
process.  The run's ``--seed`` draws everything else.  Case lengths are
the quantiles of the configuration's length distribution, shuffled by
the seed, so every seed mines the same number of events in cases of the
same sizes; the activities, case order and start times differ.

Sampling walks all open cases one position at a time and draws each next
activity with one ``searchsorted`` over the row-offset cumulative
transition probabilities, which is the same distribution as the
program's per-row comparison against the cumulative row.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Log:
    """A (case, time)-sorted log as host columns, plus its alphabet."""

    case: np.ndarray        # int64 case ids 0..num_cases-1, sorted
    act: np.ndarray         # int32 activity ids
    ts: np.ndarray          # float32 timestamps
    attrs: tuple            # extra int32 numeric columns
    num_activities: int

    @property
    def num_events(self) -> int:
        return int(self.case.size)

    @property
    def num_cases(self) -> int:
        return int(self.case[-1]) + 1 if self.case.size else 0


def process_model(num_activities: int, model_seed: int,
                  sparsity: float = 0.3):
    """(start, trans) of the configuration's process (see module doc)."""
    rng = np.random.default_rng(model_seed)
    a = num_activities
    start = rng.dirichlet(np.ones(min(a, 3)))
    start = np.concatenate([start, np.zeros(a - len(start))])
    mask = rng.random((a, a)) < sparsity
    mask |= np.eye(a, k=1, dtype=bool)
    trans = rng.random((a, a)) * mask
    trans /= np.maximum(trans.sum(1, keepdims=True), 1e-9)
    return start, trans


def case_lengths(num_cases: int, mean_len: float, max_len: int,
                 total_events: int | None = None) -> np.ndarray:
    """Quantiles of min(Geometric(1/mean_len), max_len), one per case, in
    ascending order.  With ``total_events`` the longest cases are trimmed
    or the shortest lengthened by one event until the sum matches."""
    p = 1.0 / mean_len
    u = (np.arange(num_cases) + 0.5) / num_cases
    lens = np.ceil(np.log1p(-u) / np.log1p(-p)).astype(np.int64)
    lens = np.clip(lens, 1, max_len)
    if total_events is not None:
        diff = int(total_events) - int(lens.sum())
        if diff > 0:
            room = np.flatnonzero(lens < max_len)
            if room.size < diff:
                raise ValueError("total_events exceeds max_len * num_cases")
            lens[room[:diff]] += 1
        elif diff < 0:
            room = np.flatnonzero(lens > 1)[::-1]
            if room.size < -diff:
                raise ValueError("total_events below one event per case")
            lens[room[:-diff]] -= 1
    return np.sort(lens)


def lengths(cfg: dict, rng) -> np.ndarray:
    """Case lengths in case order.  With ``batch_cases`` every batch of
    that many cases has the same multiset of lengths (so every ingest
    batch holds the same number of events), shuffled within the batch;
    the last, partial batch takes the events left to reach
    ``num_events``.  Otherwise one multiset over all cases, shuffled."""
    n, mean = int(cfg["num_cases"]), float(cfg["mean_case_length"])
    top, total = int(cfg["max_case_length"]), cfg.get("num_events")
    bc = cfg.get("batch_cases")
    if not bc:
        return rng.permutation(case_lengths(n, mean, top, total))
    bc = int(bc)
    full, rest = divmod(n, bc)
    per = round((total if total is not None else n * mean) * bc / n)
    one = case_lengths(bc, mean, top, per)
    parts = [rng.permutation(one) for _ in range(full)]
    if rest:
        left = None if total is None else int(total) - full * per
        parts.append(rng.permutation(case_lengths(rest, mean, top, left)))
    return np.concatenate(parts)


def generate(cfg: dict, seed: int) -> Log:
    """The configuration's log for ``seed`` (``cfg`` is the config file's
    ``log`` object)."""
    a = int(cfg["num_activities"])
    start, trans = process_model(a, int(cfg["model_seed"]))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0xB17]))
    lens = lengths(cfg, rng)
    n_cases = lens.size
    offsets = np.concatenate([[0], np.cumsum(lens)[:-1]])
    total = int(lens.sum())
    act = np.empty(total, np.int32)
    # row-offset cumulative probabilities: row r occupies (r, r+1]
    cum = (np.cumsum(trans, axis=1) + np.arange(a)[:, None]).ravel()
    cum_start = np.cumsum(start)
    cur = np.minimum(np.searchsorted(cum_start, rng.random(n_cases),
                                     side="right"), a - 1).astype(np.int64)
    act[offsets] = cur
    live = np.arange(n_cases)
    for t in range(1, int(lens.max())):
        keep = lens[live] > t
        live, cur = live[keep], cur[keep]
        if not live.size:
            break
        pos = np.searchsorted(cum, cur + rng.random(live.size), side="right")
        cur = np.clip(pos - cur * a, 0, a - 1)
        act[offsets[live] + t] = cur
    case = np.repeat(np.arange(n_cases, dtype=np.int64), lens)
    pos_in_case = np.arange(total, dtype=np.int64) - np.repeat(offsets, lens)
    t0 = rng.random(n_cases) * float(cfg.get("start_span", 1e6))
    ts = (t0[case] + pos_in_case).astype(np.float32)
    attrs = tuple(rng.integers(0, 1000, size=total).astype(np.int32)
                  for _ in range(int(cfg.get("extra_numeric_attrs", 0))))
    return Log(case, act, ts, attrs, a)


def to_frame(log: Log, lo: int = 0, hi: int | None = None):
    """Rows ``[lo, hi)`` as the program's ``EventFrame`` and tables."""
    from repro.core.eventframe import ACTIVITY, CASE, TIMESTAMP, EventFrame

    sl = slice(lo, hi)
    cols = {CASE: log.case[sl], ACTIVITY: log.act[sl], TIMESTAMP: log.ts[sl]}
    for k, col in enumerate(log.attrs):
        cols[f"attr{k}"] = col[sl]
    tables = {ACTIVITY: [f"act_{i:03d}" for i in range(log.num_activities)]}
    return EventFrame.from_numpy(cols), tables
