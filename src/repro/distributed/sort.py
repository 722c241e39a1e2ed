"""Distributed sort-by-case: all-to-all bucket exchange.

The paper's shifting-and-counting *assumes the dataframe is sorted by case
id*. At cluster scale the log arrives time-ordered and distributed, so the
sort itself must be distributed: each shard buckets its events by
``hash(case) % n_shards``, an all_to_all exchanges buckets (each case lands
wholly on one shard), and a local lexsort finishes. This is the classic
"exchange + local sort" — one collective pass, O(N/p log N/p) local work.

Static-shape constraint (TPU): bucket capacity is ``cap = ceil(N/p * slack)``
per (src, dst) pair; overflow is detected and reported (slack=2 default).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.eventframe import ACTIVITY, CASE, TIMESTAMP, EventFrame


def _exchange(case, act, ts, *, n_shards, cap, axis_name):
    tgt = case % n_shards                                   # destination shard
    # position of each row within its destination bucket
    onehot = jax.nn.one_hot(tgt, n_shards, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)
    slot = jnp.take_along_axis(pos, tgt[:, None], axis=1)[:, 0]
    overflow = jax.lax.pmax((slot >= cap).any().astype(jnp.int32), axis_name)
    slot = jnp.minimum(slot, cap - 1)

    def bucketize(x, fill):
        buf = jnp.full((n_shards, cap), fill, x.dtype)
        return buf.at[tgt, slot].set(x, mode="drop")

    bc = bucketize(case, -1)
    ba = bucketize(act, -1)
    bt = bucketize(ts, jnp.inf)
    # exchange: row i of my buffer goes to shard i
    bc = jax.lax.all_to_all(bc, axis_name, 0, 0, tiled=False)
    ba = jax.lax.all_to_all(ba, axis_name, 0, 0, tiled=False)
    bt = jax.lax.all_to_all(bt, axis_name, 0, 0, tiled=False)
    cc = bc.reshape(-1)
    aa = ba.reshape(-1)
    tt = bt.reshape(-1)
    order = jnp.lexsort((tt, cc))                           # case major, ts minor
    return cc[order], aa[order], tt[order], overflow


def sort_by_case_sharded(frame: EventFrame, mesh, axis_name: str = "data",
                         slack: float = 2.0):
    """Returns per-shard (case, act, ts) case-sorted arrays + overflow flag.

    Invalid slots carry case == -1 and sort to the front; downstream DFG
    treats them as non-matching (distinct sentinel per position not needed —
    they never equal a real case id and the -1 run only pairs within itself,
    contributing to bucket (a*A+a) only if act==-1 which is filtered)."""
    n = frame.nrows
    n_shards = mesh.shape[axis_name]
    local = n // n_shards
    cap = int(local * slack / n_shards + 1)

    fn = shard_map(
        functools.partial(_exchange, n_shards=n_shards, cap=cap,
                          axis_name=axis_name),
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name), P(axis_name), P()),
    )
    case = frame[CASE].astype(jnp.int32)
    act = frame[ACTIVITY].astype(jnp.int32)
    ts = frame[TIMESTAMP].astype(jnp.float32)
    return jax.jit(fn)(case, act, ts)
