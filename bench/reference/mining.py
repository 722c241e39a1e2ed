"""Plain NumPy references for every mining verb the benchmark times.

Each function takes the host columns of a (case, time)-sorted log (case
ids ``0..n-1`` in order, activity ids, float32 timestamps) and returns
the verb's result in the JSON form the service serves (``_type`` tags
for structured results, lists for tuples).  Nothing here imports the
program: the definitions follow the paper (directly-follows and
eventually-follows relations, alpha footprints and places, the
heuristics measures), the graph queries' documented semantics, and the
variant fingerprint ``h <- h*B + act + 1 (mod 2^32)``.

Integer, boolean and set outputs are exact.  Float outputs are computed
in ``fdt``: float64 for the reference, and ``ml_dtypes.bfloat16`` for
the control (the same arithmetic one precision below the float32 the
configurations state), which has to fail the comparison.
"""
from __future__ import annotations

from collections import deque

import numpy as np

BASE1 = 1_000_003           # the variant fingerprint's two bases
BASE2 = 16_777_619
VERBS = ("dfg", "discovery", "alpha", "heuristics", "activity_counts",
         "case_sizes", "case_durations", "sojourn_times", "stats",
         "variants", "performance_dfg", "eventually_follows", "graph",
         "reachability", "bottleneck_paths", "node_centrality")


class Log:
    """Derived views of one (case, time)-sorted log, computed once."""

    def __init__(self, case, act, ts, num_activities: int, num_cases: int,
                 fdt=np.float64):
        self.case = np.asarray(case, np.int64)
        self.act = np.asarray(act, np.int64)
        self.ts = np.asarray(ts, np.float32)
        self.a = int(num_activities)
        self.c = int(num_cases)
        self.fdt = fdt
        n = self.case.size
        self.same = (self.case[1:] == self.case[:-1] if n
                     else np.zeros(0, bool))
        self.first = np.concatenate([[True], ~self.same]) if n else self.same
        self.last = np.concatenate([~self.same, [True]]) if n else self.same
        self._memo = {}

    def memo(self, key, fn):
        if key not in self._memo:
            self._memo[key] = fn()
        return self._memo[key]

    # -------------------------------------------------- relations
    def dfg(self):
        def make():
            a = self.a
            src, dst = self.act[:-1][self.same], self.act[1:][self.same]
            counts = np.bincount(src * a + dst, minlength=a * a).reshape(a, a)
            return (counts, np.bincount(self.act[self.first], minlength=a),
                    np.bincount(self.act[self.last], minlength=a))
        return self.memo("dfg", make)

    def l2_counts(self):
        a = self.a
        if self.act.size < 3:
            return np.zeros((a, a), np.int64)
        inner = self.same[:-1] & self.same[1:]
        hit = inner & (self.act[:-2] == self.act[2:])
        return np.bincount(self.act[:-2][hit] * a + self.act[1:-1][hit],
                           minlength=a * a).reshape(a, a)

    def waits(self):
        """(source activity, target activity, wait) of each pair, waits in
        ``fdt`` from the timestamps as that precision holds them."""
        ts = self.ts.astype(self.fdt)
        dt = (ts[1:] - ts[:-1])[self.same]
        return self.act[:-1][self.same], self.act[1:][self.same], dt

    def eventually_follows(self):
        """efg[a, b]: pairs i < j of one case with act_i = a, act_j = b,
        by walking positions and adding each case's prefix histogram."""
        a = self.a
        efg = np.zeros((a, a), np.int64)
        starts = np.flatnonzero(self.first)
        lens = np.diff(np.concatenate([starts, [self.act.size]]))
        prefix = np.zeros((starts.size, a), np.int64)
        live = np.arange(starts.size)
        t = 0
        while live.size:
            cur = self.act[starts[live] + t]
            if t:
                for b in np.unique(cur):
                    efg[:, b] += prefix[live[cur == b]].sum(axis=0)
            prefix[live, cur] += 1
            t += 1
            live = live[lens[live] > t]
        return efg

    def variants(self):
        starts = np.flatnonzero(self.first)
        lens = np.diff(np.concatenate([starts, [self.act.size]]))
        tok = (self.act + 1).astype(np.uint32)
        h1 = np.zeros(self.c, np.uint32)
        h2 = np.zeros(self.c, np.uint32)
        cases = self.case[starts]
        with np.errstate(over="ignore"):
            for t in range(int(lens.max()) if lens.size else 0):
                live = lens > t
                v = tok[starts[live] + t]
                h1[cases[live]] = h1[cases[live]] * np.uint32(BASE1) + v
                h2[cases[live]] = h2[cases[live]] * np.uint32(BASE2) + v
        return [h1, h2, int(starts.size)]

    # ------------------------------------------------------- stats
    def activity_counts(self):
        return np.bincount(self.act, minlength=self.a)

    def case_sizes(self):
        return np.bincount(self.case, minlength=self.c)

    def case_durations(self):
        ts = self.ts.astype(self.fdt)
        lo = np.full(self.c, np.inf, self.fdt)
        hi = np.full(self.c, -np.inf, self.fdt)
        np.minimum.at(lo, self.case, ts)
        np.maximum.at(hi, self.case, ts)
        return np.where(hi >= lo, hi - lo, 0)

    def sojourn_times(self):
        src, _, dt = self.waits()
        tot = np.zeros(self.a, self.fdt)
        np.add.at(tot, src, dt)
        cnt = np.bincount(src, minlength=self.a)
        return tot / np.maximum(cnt, 1).astype(self.fdt)

    def performance_dfg(self):
        src, dst, dt = self.waits()
        a = self.a
        tot = np.zeros(a * a, self.fdt)
        np.add.at(tot, src * a + dst, dt)
        counts = self.dfg()[0]
        return [counts, tot.reshape(a, a)
                / np.maximum(counts, 1).astype(self.fdt)]


# ---------------------------------------------------------- finalizes
def _sorted_ids(mask) -> list:
    return [int(i) for i in np.flatnonzero(mask)]


def footprint(counts, min_count: int = 1) -> dict:
    d = counts >= min_count
    return {"_type": "Footprint", "direct": d, "causal": d & ~d.T,
            "parallel": d & d.T, "choice": ~d & ~d.T}


def alpha_places(causal, choice) -> list:
    """Maximal (A, B) with A x B causal and A, B each in choice (so with
    no self-loops): every valid pair grows from a valid singleton pair by
    adding one activity at a time, so a search over such extensions
    reaches all of them."""
    n = causal.shape[0]
    ok_a = [a for a in range(n) if choice[a, a]]

    def valid(aa, bb):
        al, bl = sorted(aa), sorted(bb)
        return bool(causal[np.ix_(al, bl)].all()
                    and choice[np.ix_(al, al)].all()
                    and choice[np.ix_(bl, bl)].all())

    seen = {(frozenset([a]), frozenset([b]))
            for a in ok_a for b in ok_a if causal[a, b]}
    todo = deque(seen)
    while todo:
        aa, bb = todo.popleft()
        for x in ok_a:
            for cand in ((aa | {x}, bb), (aa, bb | {x})):
                if cand not in seen and valid(*cand):
                    seen.add(cand)
                    todo.append(cand)
    maximal = [p for p in seen
               if not any(q != p and p[0] <= q[0] and p[1] <= q[1]
                          for q in seen)]
    return sorted(([sorted(a), sorted(b)] for a, b in maximal))


def alpha(counts, starts, ends) -> dict:
    fp = footprint(counts)
    return {"_type": "AlphaModel", "num_activities": int(counts.shape[0]),
            "places": alpha_places(fp["causal"], fp["choice"]),
            "start_activities": _sorted_ids(starts),
            "end_activities": _sorted_ids(ends), "footprint": fp}


def heuristics(counts, l2c, starts, ends, fdt, dependency=0.5, l2_thr=0.5,
               and_thr=0.65, min_count=1) -> dict:
    a = counts.shape[0]
    eye = np.eye(a, dtype=bool)
    c = counts.astype(fdt)
    one = fdt(1.0)
    dep = (c - c.T) / (c + c.T + one)
    diag = np.diag(c)
    dep = np.where(eye, (diag / (diag + one))[:, None], dep).astype(fdt)
    c2 = l2c.astype(fdt)
    l2 = np.where(eye, fdt(0.0), (c2 + c2.T) / (c2 + c2.T + one)).astype(fdt)
    and_m = ((c + c.T)[None, :, :]
             / (c[:, :, None] + c[:, None, :] + one))
    keep = (dep >= dependency) & ~eye & (counts >= min_count)
    loops1 = (np.diag(dep) >= dependency) & (np.diag(counts) >= min_count)
    no_l1 = ~loops1[:, None] & ~loops1[None, :]
    keep2 = ((l2 >= l2_thr) & ((l2c + l2c.T) >= min_count) & no_l1 & ~eye)
    graph = keep | (eye & loops1[:, None]) | keep2 | keep2.T
    both = graph[:, :, None] & graph[:, None, :] & ~eye[None, :, :]
    return {"_type": "HeuristicsNet", "dependency": dep, "l2": l2,
            "graph": graph, "and_bindings": both & (and_m >= and_thr),
            "start_activities": _sorted_ids(starts),
            "end_activities": _sorted_ids(ends)}


def graph_freq(counts, starts, ends):
    a = counts.shape[0]
    freq = np.zeros((a + 2, a + 2), np.int64)
    freq[:a, :a] = counts
    freq[a, :a] = starts
    freq[:a, a + 1] = ends
    return freq


def reachability(freq) -> dict:
    n = freq.shape[0]
    reach = np.eye(n, dtype=bool) | (freq > 0)
    for k in range(n):
        reach |= reach[:, k:k + 1] & reach[k:k + 1, :]
    return {"_type": "Reachability", "k": max(n - 1, 1), "mask": reach}


def bottleneck_paths(freq) -> dict:
    """Floyd-Warshall hop distances and widest (max-min) capacities; the
    source -> sink corridor is a hop-shortest path over the edges at least
    as wide as the widest capacity, successors taken in node order."""
    n = freq.shape[0]
    adj = freq > 0
    eye = np.eye(n, dtype=bool)
    short = np.where(eye, 0.0, np.where(adj, 1.0, np.inf))
    wide = np.where(eye, np.inf, np.where(adj, freq.astype(np.float64),
                                          -np.inf))
    for k in range(n):
        short = np.minimum(short, short[:, k:k + 1] + short[k:k + 1, :])
        wide = np.maximum(wide, np.minimum(wide[:, k:k + 1],
                                           wide[k:k + 1, :]))
    src, dst = n - 2, n - 1
    v = wide[src, dst]
    path = []
    if np.isfinite(v) and v > 0:
        prev = {src: None}
        frontier = [src]
        while frontier and dst not in prev:
            nxt = []
            for u in frontier:
                for j in np.flatnonzero(freq[u] >= v):
                    if int(j) not in prev:
                        prev[int(j)] = u
                        nxt.append(int(j))
            frontier = nxt
        if dst in prev:
            path = [dst]
            while path[-1] != src:
                path.append(prev[path[-1]])
            path = path[::-1]
    return {"_type": "BottleneckPaths", "weights": "frequency",
            "shortest": short, "widest": wide, "path": path,
            "bottleneck": float(v) if path else 0.0}


def node_centrality(freq, fdt, iters: int = 16) -> dict:
    """Degrees, and the flow vector of ``iters`` power steps over the
    row-normalized transition matrix (rows with no mass restart at the
    source), L1-normalized after every step."""
    n = freq.shape[0]
    f = freq.astype(fdt)
    rowsum = f.sum(axis=1, keepdims=True)
    restart = np.zeros((1, n), fdt)
    restart[0, n - 2] = 1
    p = np.where(rowsum > 0, f / np.maximum(rowsum, fdt(1.0)), restart)
    p = p.astype(fdt)
    x = np.full(n, fdt(1.0) / fdt(n), fdt)
    for _ in range(iters):
        x = (x @ p).astype(fdt)
        x = (x / max(x.sum(dtype=fdt), fdt(1e-30))).astype(fdt)
    return {"_type": "Centrality", "in_degree": freq.sum(axis=0),
            "out_degree": freq.sum(axis=1), "flow": x, "iters": iters}


def _graph(freq) -> dict:
    return {"_type": "ProcessGraph", "freq": freq,
            "num_activities": int(freq.shape[0] - 2), "perf": None,
            "labels": None}


def verb(log: Log, name: str):
    """One verb's reference result (JSON form, arrays as NumPy)."""
    counts, starts, ends = log.dfg()
    dfg = {"_type": "DFG", "counts": counts, "starts": starts, "ends": ends}
    if name == "dfg":
        return dfg
    if name == "discovery":
        return {"_type": "DiscoveryState", "dfg": dfg,
                "l2_counts": log.memo("l2", log.l2_counts)}
    if name == "alpha":
        return log.memo("alpha", lambda: alpha(counts, starts, ends))
    if name == "heuristics":
        return heuristics(counts, log.memo("l2", log.l2_counts), starts,
                          ends, log.fdt)
    if name in ("activity_counts", "case_sizes", "case_durations",
                "sojourn_times", "eventually_follows", "performance_dfg",
                "variants"):
        return log.memo(name, getattr(log, name))
    if name == "stats":
        return {k: verb(log, k) for k in ("activity_counts", "case_sizes",
                                          "case_durations", "sojourn_times")}
    freq = graph_freq(counts, starts, ends)
    if name == "graph":
        return _graph(freq)
    if name == "reachability":
        return reachability(freq)
    if name == "bottleneck_paths":
        return bottleneck_paths(freq)
    if name == "node_centrality":
        return node_centrality(freq, log.fdt)
    raise KeyError(f"no reference for verb {name!r}")


def profile(log: Log) -> dict:
    return {v: verb(log, v) for v in VERBS}


def served_graph(log: Log) -> dict:
    """The body of ``/graph?query=bottleneck_paths`` without the labels."""
    counts, starts, ends = log.dfg()
    freq = graph_freq(counts, starts, ends)
    a = counts.shape[0]
    return {"graph": {"freq": freq, "perf": None, "source": a,
                      "sink": a + 1},
            "query": bottleneck_paths(freq)}
