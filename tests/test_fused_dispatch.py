"""Fused encode→sort dispatch: parity, dispatch counts, batched stream
sorts, and the external sort's compile budget.

The tentpole invariants this file pins:

* the fused chain (raw columns in, encode traced into the program) is
  **bit-exact** against eager encode-then-sort for every codec — signed
  ints, floats with NaN/±0.0/denormals, bool, desc inversion, >32-bit
  multi-word composites;
* the executor's ``encode=`` hook produces the same results on the jnp
  AND Pallas backends;
* one warm ``order_by`` costs exactly one used-bits probe plus ONE fused
  chain execution (counted at the repo's own jit sites);
* the stream path's batched partition sorts are bit-identical to the
  serial per-partition path under a tight budget, and actually engage on
  skewed data;
* an external-sort call compiles one sort chain per (length, sort-bits)
  bucket, not one per partition.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import JnpBackend, PallasBackend, PlanExecutor, dispatch
from repro.core.sort_plan import make_sort_plan
from repro.query import Table, order_by
from repro.query.operators import (
    _key_data,
    _normalize_by,
    _rowid_chain,
    _segmented_chain,
    sort_rowids,
    sort_rowids_fused,
)
from repro.stream import ArraySource, MemoryBudget, external_sort
from repro.stream.chunks import RunStore
from repro.stream.external import row_cost_bytes, stream_sorted_words


# ---------------------------------------------------------------------------
# fused ≡ eager parity, every codec family
# ---------------------------------------------------------------------------

def _codec_tables():
    rng = np.random.default_rng(11)
    n = 2048
    f32 = rng.standard_normal(n).astype(np.float32)
    f32[:64] = np.nan
    f32[64:96] = 0.0
    f32[96:128] = -0.0
    f32[128:160] = np.float32(1e-40)  # denormal
    f32[160:192] = -np.float32(1e-40)
    f32[192:224] = [np.inf, -np.inf] * 16
    f64 = rng.standard_normal(n)
    f64[:64] = np.nan
    f64[64:96] = -0.0
    f64[96:128] = 5e-324  # denormal
    return {
        "int32_asc": ({"a": rng.integers(-2**31, 2**31, n,
                                         dtype=np.int64).astype(np.int32)},
                      [("a", "asc")]),
        "int32_desc": ({"a": rng.integers(-1000, 1000, n).astype(np.int32)},
                       [("a", "desc")]),
        "bool": ({"a": rng.random(n) < 0.5}, [("a", "asc")]),
        "float32_special": ({"a": f32}, [("a", "desc")]),
        "float64_multiword": ({"a": f64}, [("a", "asc")]),
        "composite_wide": ({"a": rng.integers(0, 1 << 20, n).astype(np.int32),
                            "b": f32, "c": rng.integers(0, 4, n).astype(
                                np.int32)},
                           [("a", "asc"), ("b", "desc"), ("c", "asc")]),
        "low_entropy": ({"a": rng.integers(0, 7, n).astype(np.int32)},
                        [("a", "asc")]),
        "strided": ({"a": (rng.integers(0, 64, n) * 4096).astype(np.int32)},
                    [("a", "desc")]),
        "constant": ({"a": np.full(n, 42, np.int32)}, [("a", "asc")]),
    }


@pytest.mark.parametrize("case", sorted(_codec_tables()))
def test_fused_equals_eager_encode_then_sort(case):
    """sort_rowids_fused (raw columns, probe-narrowed, encode in-trace)
    must return bit-identical (sorted_words, rowids) to the eager path
    (host-encoded words through sort_rowids) — the narrowed bits are
    row-invariant, so the permutation cannot differ."""
    cols, by = _codec_tables()[case]
    t = Table(cols)
    codec, prepped = _key_data(t, _normalize_by(by), None)
    sw_f, rid_f = sort_rowids_fused(codec, prepped)
    words = codec.encode_fn(prepped)
    sw_e, rid_e = sort_rowids(jnp.asarray(words), codec.bits)
    np.testing.assert_array_equal(np.asarray(sw_f), np.asarray(sw_e))
    np.testing.assert_array_equal(np.asarray(rid_f), np.asarray(rid_e))


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_executor_encode_hook_parity(backend):
    """run/run_pairs/run_argsort with the fused ``encode=`` hook must
    equal pre-encoding on the host, on both backends."""
    be = JnpBackend() if backend == "jnp" else PallasBackend(interpret=True)
    ex = PlanExecutor(be)
    rng = np.random.default_rng(5)
    n, p = 1500, 20
    raw = jnp.asarray(rng.integers(0, 1 << p, n,
                                   dtype=np.int64).astype(np.uint32))
    flip = jnp.uint32((1 << p) - 1)
    encode = lambda x: x ^ flip  # order-reversing, stays within p bits
    plan = make_sort_plan(n, p)
    pre = encode(raw)
    vals = jnp.arange(n, dtype=jnp.int32)

    np.testing.assert_array_equal(
        np.asarray(ex.run(raw, plan, encode=encode)),
        np.asarray(ex.run(pre, plan)))
    k_f, v_f = ex.run_pairs(raw, vals, plan, encode=encode)
    k_e, v_e = ex.run_pairs(pre, vals, plan)
    np.testing.assert_array_equal(np.asarray(k_f), np.asarray(k_e))
    np.testing.assert_array_equal(np.asarray(v_f), np.asarray(v_e))
    np.testing.assert_array_equal(
        np.asarray(ex.run_argsort(raw, plan, encode=encode)),
        np.asarray(ex.run_argsort(pre, plan)))


def test_order_by_is_one_probe_plus_one_chain():
    """A warm in-memory order_by costs exactly one used-bits probe and
    ONE fused chain execution — no per-word, per-pass, or per-column
    dispatches at the repo's counted jit sites."""
    rng = np.random.default_rng(0)
    t = Table({"k": rng.integers(0, 1 << 10, 4096).astype(np.int32),
               "v": rng.standard_normal(4096).astype(np.float32)})
    by = [("k", "asc"), ("v", "desc")]
    order_by(t, by)  # pay compiles and lru fills
    with dispatch.track() as seen:
        order_by(t, by)
    execs = {k: v for k, v in seen.items()
             if k.startswith("query.") and not k.endswith(":compiles")}
    assert execs == {"query.probe": 1, "query.chain": 1}, execs


# ---------------------------------------------------------------------------
# stream: batched partition sorts ≡ serial, and they actually engage
# ---------------------------------------------------------------------------

class _SerialOnlyStore(RunStore):
    """Disk store that refuses batched sorts — the serial reference."""

    supports_batched_sorts = False


def _skewed_keys():
    """Heavy single values (oversized bins) interleaved with sparse
    ranges: the distribution whose tiny flushed partitions share one
    (padded length, sort bits) bucket across oversized separators."""
    rng = np.random.default_rng(3)
    parts = []
    for b in range(0, 1024, 128):
        parts.append(np.full(3000, (b << 22) | 977, np.uint32))
        parts.append(((b + 1 + rng.integers(0, 120, 40)) << 22).astype(
            np.uint32) | rng.integers(0, 1 << 22, 40).astype(np.uint32))
    return rng.permutation(np.concatenate(parts)).astype(np.uint32)


def test_stream_batched_equals_serial_partition_sorts(tmp_path):
    """Under a tight budget on skewed data the batched grouped dispatch
    must engage (≥1 segmented-chain execution) and yield byte-identical
    output to a store that only sorts serially."""
    keys = _skewed_keys()
    row_bytes = row_cost_bytes(1)

    def run(store_cls, root):
        budget = MemoryBudget(12 << 10)
        src = ArraySource(keys, budget.rows(row_bytes))
        store = store_cls(str(root))
        try:
            chunks_fn = lambda: (  # noqa: E731
                (c.reshape(-1, 1).view(np.uint32), ()) for c in src.chunks())
            with dispatch.track() as seen:
                out = np.concatenate([
                    w[:, 0] for w, _ in stream_sorted_words(
                        chunks_fn, 32, budget, store, row_bytes)])
        finally:
            store.close()
        assert budget.peak_bytes <= budget.limit_bytes
        return out, seen

    batched_out, batched_seen = run(RunStore, tmp_path / "batched")
    serial_out, serial_seen = run(_SerialOnlyStore, tmp_path / "serial")
    np.testing.assert_array_equal(batched_out, serial_out)
    np.testing.assert_array_equal(batched_out, np.sort(keys))
    assert batched_seen.get("query.segmented_chain", 0) >= 1, (
        "the skewed distribution should have exercised the batched "
        f"dispatch, saw {batched_seen}")
    assert serial_seen.get("query.segmented_chain", 0) == 0
    # batching replaces a group of serial chain dispatches with one
    assert (batched_seen.get("query.chain", 0)
            + batched_seen.get("query.segmented_chain", 0)
            < serial_seen.get("query.chain", 0))


def test_external_sort_compiles_one_chain_per_bucket():
    """One external-sort call compiles one sort chain per distinct
    (padded length, sort-bits) bucket, counted at the chain's jit site;
    8 budget-packed uniform partitions share a handful of buckets, so
    they compile a handful of chains, never one per partition."""
    _rowid_chain.cache_clear()  # fresh jit caches: every compile counts
    _segmented_chain.cache_clear()
    rng = np.random.default_rng(9)
    n = 1 << 14
    keys = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    budget = MemoryBudget(n * 4 // 8)
    src = ArraySource(keys, budget.rows(row_cost_bytes(1)))
    with dispatch.track() as seen:
        chunks = list(external_sort(src, 32, budget))
    tags = ("query.chain", "query.segmented_chain")
    runs = sum(seen.get(t, 0) for t in tags)
    compiles = sum(seen.get(f"{t}:compiles", 0) for t in tags)
    assert np.array_equal(np.concatenate(chunks), np.sort(keys))
    assert len(chunks) >= 8, "expected ≥8 partitions for this ratio"
    assert runs >= 2, f"{runs} chain runs for {len(chunks)} partitions"
    assert 0 < compiles <= 4, (
        f"{compiles} chain compiles for {len(chunks)} partitions: "
        "plans regressed to one program per partition")


# ---------------------------------------------------------------------------
# dispatch accounting unit tests
# ---------------------------------------------------------------------------

def test_dispatch_wrap_counts_calls_and_compiles():
    import jax

    fn = dispatch.wrap("test.unit", jax.jit(lambda x: x + 1))
    with dispatch.track() as seen:
        fn(jnp.arange(4))      # traces: 1 call, 1 compile
        fn(jnp.arange(4))      # cached: 1 call
        fn(jnp.arange(8))      # new shape: 1 call, 1 compile
    assert seen["test.unit"] == 3
    assert seen["test.unit:compiles"] == 2


def test_dispatch_track_is_scoped():
    dispatch.record("test.scoped")
    with dispatch.track() as seen:
        dispatch.record("test.scoped")
        dispatch.record("test.scoped")
    assert seen["test.scoped"] == 2
    assert dispatch.counts()["test.scoped"] >= 3
