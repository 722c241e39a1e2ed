"""Every Pallas kernel of the mining path compiles for a TPU v5e.

Interpret mode (the rest of the suite) cannot see what the TPU's compiler
refuses: blocks that break the (8, 128) tiling, vector shape casts, or
more VMEM than a kernel may use.  These tests compile each kernel with
``interpret=False`` for one chip of a *described* ``v5e:2x2`` topology —
nothing runs — at the widths the chip smoke run uses: 100,000-row groups,
26 activities, a case capacity of 10^6, and graph alphabets of 28 and 300.
One more compiles the sharded engine's kernels inside ``shard_map`` over
all four described chips.  Each also finds its kernel in the compiled
module under the name ``pallas_call(name=)`` pins: the name the device
trace shows, which the benchmark's roofline share matches.

The topology is described inside a fixture, never at import time: only one
process may load the TPU library, and test workers import every file.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.graph_ops import SEMIRINGS, semiring_matmul_pallas
from repro.kernels.segment_ops.histogram import histogram_pallas
from repro.kernels.segment_ops.pair_count import pair_count_pallas
from repro.kernels.segment_ops.segment_reduce import segment_reduce_pallas
from repro.kernels.segment_ops.segmented_scan import (
    segmented_affine_pallas, segmented_polyhash_pallas,
    segmented_sum_scan_pallas)

ROWS = 100_000            # one EDF row group of the smoke run
ACTIVITIES = 26           # paper Table 6 logs
CASES = 1_000_000         # case capacity of the L1 log


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """Shape builder on one described chip, with the persistent compile
    cache off: a described chip's programs cannot be read back from it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one_chip = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one_chip)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _named(hlo: str, name: str) -> bool:
    """The compiled module holds an instruction named ``name`` (the name
    the device trace shows and the benchmark's kernel table matches)."""
    return re.search(rf"^\s*(ROOT )?%{name}(\.\d+)? = ", hlo, re.M) is not None


def _compile(kernel, name, *shapes, **static):
    lowered = kernel.lower(*shapes, interpret=False, **static)
    # pinned by ``pallas_call(name=)``, not taken from a wrapper's name
    assert f'kernel_name = "{name}"' in lowered.as_text()
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo        # the Mosaic kernel, not a fallback
    assert _named(hlo, name)


def test_pair_count_compiles(spec):
    ev = spec((ROWS,), jnp.int32)
    _compile(pair_count_pallas, "pair_count_pallas", ev, ev,
             spec((ROWS,), jnp.float32),
             num_src=ACTIVITIES, num_dst=ACTIVITIES)


@pytest.mark.parametrize("bins", [ACTIVITIES, ACTIVITIES * ACTIVITIES])
def test_histogram_compiles(spec, bins):
    _compile(histogram_pallas, "histogram_pallas", spec((ROWS,), jnp.int32),
             spec((ROWS,), jnp.int32), num_bins=bins)


@pytest.mark.parametrize("op,dtype", [("sum", jnp.int32),
                                      ("min", jnp.float32),
                                      ("max", jnp.uint32)])
def test_segment_reduce_compiles(spec, op, dtype):
    _compile(segment_reduce_pallas, "segment_reduce_pallas",
             spec((ROWS,), dtype), spec((ROWS,), jnp.int32),
             num_segments=CASES, op=op)


def test_segmented_polyhash_compiles(spec):
    _compile(segmented_polyhash_pallas, "segmented_affine_pallas",
             spec((ROWS,), jnp.uint32), spec((ROWS,), jnp.bool_),
             spec((), jnp.uint32), base=1_000_003)


def test_segmented_affine_compiles(spec):
    ev = spec((ROWS,), jnp.uint32)
    _compile(segmented_affine_pallas, "segmented_affine_pallas", ev, ev,
             spec((ROWS,), jnp.bool_), spec((), jnp.uint32))


def test_segmented_sum_scan_compiles(spec):
    _compile(segmented_sum_scan_pallas, "segmented_sum_scan_pallas",
             spec((ROWS, ACTIVITIES), jnp.float32),
             spec((ROWS,), jnp.bool_), spec((ACTIVITIES,), jnp.float32))


def test_kernels_compile_inside_shard_map(topo, spec):
    """The sharded variants path: the affine scan and ``segment_reduce``
    traced inside ``shard_map`` over all four described chips, where the
    varying-axes check asks each ``pallas_call`` output for its ``vma``."""
    mesh = Mesh(np.array(topo.devices), ("data",))
    rows = NamedSharding(mesh, P("data"))

    def ev(dtype):
        return jax.ShapeDtypeStruct((mesh.size * ROWS,), dtype, sharding=rows)

    def shard(mul, add, starts, seg):
        seed = jax.lax.pcast(jnp.uint32(0), "data", to="varying")
        ys, _ = segmented_affine_pallas(mul, add, starts, seed,
                                        interpret=False)
        fp = segment_reduce_pallas(ys, seg, CASES, "max", interpret=False)
        return jax.lax.psum(fp, "data")

    fn = jax.jit(jax.shard_map(shard, mesh=mesh, in_specs=(P("data"),) * 4,
                               out_specs=P()))
    hlo = fn.lower(ev(jnp.uint32), ev(jnp.uint32), ev(jnp.bool_),
                   ev(jnp.int32)).compile().as_text()
    assert mesh.size == 4 and "tpu_custom_call" in hlo
    assert _named(hlo, "segmented_affine_pallas")
    assert _named(hlo, "segment_reduce_pallas")


@pytest.mark.parametrize("nodes", [ACTIVITIES + 2, 300])
@pytest.mark.parametrize("semiring", SEMIRINGS)
def test_semiring_matmul_compiles(spec, semiring, nodes):
    m = spec((nodes, nodes), jnp.float32)
    _compile(semiring_matmul_pallas, "semiring_matmul_pallas", m, m,
             semiring=semiring)
