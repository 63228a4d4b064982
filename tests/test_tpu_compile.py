"""Compile the main-path kernels and the jitted sort for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described topology.
A compile that passes is not a chip run; it catches what interpret mode
cannot (primitives with no Pallas TPU lowering, unaligned tiles, VMEM
overuse).  The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.executor import SORT_PLACEMENT_MIN_ROWS
from repro.core.fractal_sort import fractal_sort
from repro.core.sort_plan import make_sort_plan
from repro.kernels.fractal_histogram import fractal_histogram
from repro.kernels.fractal_rank import fractal_rank_counts, fractal_rank_kernel
from repro.kernels.fractal_reconstruct import fractal_reconstruct
from repro.kernels.moe_dispatch import moe_dispatch
from repro.query.codec import ColumnSpec, CompositeCodec, UIntCodec
from repro.query.operators import _fused_chain

N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n_bins", [16, 256])
def test_histogram_compiles(one_chip, n_bins):
    c = _compile(one_chip,
                 lambda k: fractal_histogram(k, n_bins, interpret=False),
                 ((N,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("n_bins", [16, 256])
def test_onehot_rank_kernel_compiles(one_chip, n_bins):
    c = _compile(one_chip,
                 lambda k, s: fractal_rank_kernel(k, s, n_bins,
                                                  interpret=False),
                 ((N,), jnp.int32), ((n_bins,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_reconstruct_compiles(one_chip):
    c = _compile(one_chip,
                 lambda c, t: fractal_reconstruct(c, t, 256, 24,
                                                  interpret=False),
                 ((256,), jnp.int32), ((N,), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_moe_dispatch_compiles(one_chip):
    """qwen3-moe-30b-a3b's dispatch: 128 experts, 65536 routed ids."""
    c = _compile(one_chip, lambda i: moe_dispatch(i, 128, interpret=False),
                 ((1 << 16,), jnp.int32))
    assert c.as_text().count("tpu_custom_call") >= 2  # histogram + rank


def test_fractal_sort_compiles(one_chip):
    """The users' main path (JnpBackend passes) at the default plan; its
    scratch stays a few bytes per key (the paper-native 16-bit plan's
    is ~140 B/key, an open item)."""
    plan = make_sort_plan(N, 32)
    c = _compile(one_chip, lambda k: fractal_sort(k, 32, plan=plan),
                 ((N,), jnp.uint32))
    assert c.memory_analysis().temp_size_in_bytes < 16 * N


def _computations(hlo: str) -> dict:
    """Computation name -> its instruction lines, from HLO text."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            name = re.search(r"%[\w.\-]+", line).group(0)
            comps[name] = []
        elif line == "}":
            name = None
        elif name is not None:
            comps[name].append(line)
    return comps


def test_rank_scan_holds_no_gather_or_reduce_window(one_chip):
    """The one-hot rank's group scan works on masks and matmuls: the while
    bodies of the default p=16 sort, and every computation they call,
    hold no reduce-window (a cumsum lowers to one on TPU) and no gather.
    The reconstruct's binary search (``searchsorted``) is a while loop
    of its own, outside the rank, and is not checked."""
    c = _compile(one_chip, lambda k: fractal_sort(k, 16), ((N,), jnp.int32))
    comps = _computations(c.as_text())
    todo = [m.group(1) for lines in comps.values() for line in lines
            if " while(" in line and "searchsorted" not in line
            for m in [re.search(r"body=(%[\w.\-]+)", line)]]
    assert len(todo) == len(make_sort_plan(N, 16).passes)
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            assert not re.search(r"\s(reduce-window|gather)\(", line), line
            todo += [r for r in re.findall(r"%[\w.\-]+", line)
                     if r in comps]
    assert any(re.search(r"\s(convolution|dot)\(", line)
               for name in seen for line in comps[name])


@pytest.mark.parametrize("active", [26, 25])
def test_join_key_sort_fits_a_chip(one_chip, active):
    """The 26-bit orderkey sort of TPC-H Q3's second join at 2^25 rows
    (LINEITEM's 32.3M kept rows at SF 10 round up to it): at full width
    the pairs path, narrowed to 25 varying bits the argsort path.  Each
    holds its code words as 1-D columns, a few bytes a row: a gathered
    ``(n, 1)`` uint32 matrix is laid out 128 lanes a row on a TPU, 16 GiB
    here, more than the chip holds."""
    n = 1 << 25
    codec = CompositeCodec([ColumnSpec(UIntCodec(26))])
    chain = _fused_chain(codec, ((0, active),),
                         (make_sort_plan(n, active),), active == 26)
    c = _compile(one_chip, lambda k: chain.__wrapped__((k,)),
                 ((n,), jnp.int32))
    mem = c.memory_analysis()
    assert mem.output_size_in_bytes <= 8 * n + 1024  # sorted words, row ids
    assert mem.temp_size_in_bytes < 24 * n


@pytest.mark.parametrize("path,n", [("bulk", 1 << 23), ("pairs", 1 << 25),
                                    ("pairs", 1_460_000)])
def test_each_pass_places_by_one_sort(one_chip, path, n):
    """Lowered for a TPU, a pass of ``SORT_PLACEMENT_MIN_ROWS`` rows or
    more places its keys and payloads with one sort keyed by rank and no
    scatter (which XLA lowers there to a sort plus a scatter of the
    sorted values, once per array): the default p=16 sort holds one
    two-operand sort a pass, the 26-bit pairs chain of Q3's second join
    one three-operand sort (rank, key, row id) a pass.  Below, as for
    the 1.46M rows of Q3's first join, each array keeps its native
    scatter and the program holds no sort."""
    if path == "bulk":
        operands, passes = 2, len(make_sort_plan(n, 16).passes)
        c = _compile(one_chip, lambda k: fractal_sort(k, 16),
                     ((n,), jnp.int32))
    else:
        operands = 3
        plan = make_sort_plan(n, 26)
        passes = len(plan.passes)
        chain = _fused_chain(CompositeCodec([ColumnSpec(UIntCodec(26))]),
                             ((0, 26),), (plan,), True)
        c = _compile(one_chip, lambda k: chain.__wrapped__((k,)),
                     ((n,), jnp.int32))
    hlo = c.as_text()
    sorts = [len(args.split(","))
             for args in re.findall(r"\ssort\(([^)]*)\)", hlo)]
    scatters = len(re.findall(r"\sscatter\(", hlo))
    if n >= SORT_PLACEMENT_MIN_ROWS:
        assert (sorts, scatters) == ([operands] * passes, 0)
    else:
        assert (sorts, scatters) == ([], (operands - 1) * passes)


def test_scatter_engine_raises_compiled():
    """The scatter kernel has no TPU lowering: asking for it compiled is
    an error, never a silent switch to the one-hot kernel."""
    digit = jnp.asarray(np.arange(64) % 16, jnp.int32)
    with pytest.raises(NotImplementedError, match="no Pallas TPU lowering"):
        fractal_rank_counts(digit, 16, block=32, interpret=False,
                            engine="scatter")
