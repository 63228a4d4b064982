"""PlanExecutor / PassBackend: the chunk-parallel one-hot and sorted-tile
scatter rank engines vs the serial-scan oracle, backend equivalence
(jnp == pallas-interpret == distributed on a 1-device mesh) including
mixed per-pass engine hints, the segment-aware grouped-trailing mode,
the distributed overflow per-run reset, and the empty-input guard."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    DigitPass,
    JnpBackend,
    PallasBackend,
    PlanExecutor,
    SortPlan,
    fractal_argsort,
    fractal_rank,
    fractal_rank_scatter,
    fractal_rank_serial,
    fractal_sort,
    fractal_sort_batched,
    fractal_sort_pairs,
    make_sort_plan,
)
from repro.core.executor import sort_placement

# Both parallel engines are property-tested against the same serial-scan
# oracle: same contract, one-hot vs sorted-tile arithmetic.
ENGINES = [("onehot", fractal_rank), ("scatter", fractal_rank_scatter)]
ENGINE_IDS = [name for name, _ in ENGINES]
ENGINE_FNS = [fn for _, fn in ENGINES]


# --- parallel rank engines == serial-scan oracle -----------------------------


def _assert_rank_triples_equal(a, b, ctx):
    for got, want in zip(a, b):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=str(ctx))


@pytest.mark.parametrize("engine", ENGINE_FNS, ids=ENGINE_IDS)
@pytest.mark.parametrize("n", [1, 17, 63, 64, 65, 1000, 4097, 70001])
@pytest.mark.parametrize("n_bins", [2, 16, 256, 8])
def test_parallel_rank_matches_serial_across_chunk_boundaries(
        rng, engine, n, n_bins):
    """Non-divisible sizes: chunk/tile (batch=64) and group boundaries
    land mid-stream (70001 keys span several one-hot groups at every
    width); the carry handoff must be exact at every boundary."""
    d = jnp.asarray(rng.integers(0, n_bins, n).astype(np.int32))
    _assert_rank_triples_equal(
        engine(d, n_bins, batch=64),
        fractal_rank_serial(d, n_bins, batch=64), (n, n_bins))


@pytest.mark.parametrize("engine", ENGINE_FNS, ids=ENGINE_IDS)
@pytest.mark.parametrize("dist", ["all_equal", "two_hot", "ramp",
                                  "all_equal_groups"])
def test_parallel_rank_matches_serial_adversarial(rng, engine, dist):
    """All-equal keys drive every chunk's arrival count to chunk - 1;
    ``all_equal_groups`` runs them through several one-hot groups."""
    n, n_bins = (98309 if dist == "all_equal_groups" else 5000), 16
    if dist.startswith("all_equal"):
        d = np.full(n, 7, np.int32)
    elif dist == "two_hot":
        d = np.where(rng.random(n) < 0.95, 3, 12).astype(np.int32)
    else:
        d = (np.arange(n) % n_bins).astype(np.int32)
    d = jnp.asarray(d)
    _assert_rank_triples_equal(engine(d, n_bins, batch=128),
                               fractal_rank_serial(d, n_bins, batch=128),
                               dist)


@pytest.mark.parametrize("engine", ENGINE_FNS, ids=ENGINE_IDS)
def test_parallel_rank_streaming_carry_and_bin_start(rng, engine):
    """carry_in/bin_start injection (the streaming + distributed modes)
    must thread identically through every engine.  Carries and starts
    past 2**24 (odd ones included) would round through float32: the
    one-hot engine must keep them int32."""
    n_bins = 16
    d = jnp.asarray(rng.integers(0, n_bins, 3000).astype(np.int32))
    ci = jnp.asarray(rng.integers(0, 50, n_bins).astype(np.int32))
    bs = jnp.asarray(rng.integers(0, 100, n_bins).astype(np.int32))
    big = 1 << 25
    for kw in ({"carry_in": ci}, {"bin_start": bs},
               {"carry_in": ci, "bin_start": bs},
               {"carry_in": ci + big + 1, "bin_start": bs + 2 * big + 1}):
        _assert_rank_triples_equal(engine(d, n_bins, batch=64, **kw),
                                   fractal_rank_serial(d, n_bins, batch=64,
                                                       **kw), list(kw))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 3000), st.sampled_from([4, 64, 1024]),
       st.sampled_from([2, 16, 128]))
def test_parallel_rank_property(n, batch, n_bins):
    rng = np.random.default_rng(n * 13 + batch + n_bins)
    d = jnp.asarray(rng.integers(0, n_bins, n).astype(np.int32))
    want = fractal_rank_serial(d, n_bins, batch=batch)
    for _, engine in ENGINES:
        got = engine(d, n_bins, batch=batch)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_scatter_rank_wide_bins_both_hist_paths(rng):
    """The scatter engine switches between searchsorted boundary probes
    (narrow digits) and the flat bincount (wide digits); both must match
    the oracle — including bin counts the probes must not truncate."""
    n = 3000
    for n_bins, batch in [(2048, 4096), (4096, 256), (65536, 8192)]:
        d = jnp.asarray(rng.integers(0, n_bins, n).astype(np.int32))
        _assert_rank_triples_equal(
            fractal_rank_scatter(d, n_bins, batch=batch),
            fractal_rank_serial(d, n_bins, batch=batch), (n_bins, batch))


# --- backend equivalence over the same plans ---------------------------------


@pytest.mark.parametrize("n,p,w", [(3000, 16, None), (2048, 32, None),
                                   (1000, 12, 6), (4096, 32, 8)])
def test_jnp_and_pallas_backends_agree(rng, n, p, w):
    keys = rng.integers(0, 1 << p, n, dtype=np.uint64).astype(np.uint32)
    dtype = jnp.uint32 if p == 32 else jnp.int32
    arr = jnp.asarray(keys, dtype)
    plan = make_sort_plan(n, p, max_bins_log2=w)
    via_jnp = PlanExecutor(JnpBackend()).run(arr, plan)
    via_pallas = PlanExecutor(PallasBackend(interpret=True)).run(arr, plan)
    want = np.sort(keys.astype(np.uint64))
    # the reconstruct kernel emits int32 bit patterns (exact as uint32 —
    # the entry-point wrappers cast); normalize both backends through u32
    for got in (via_jnp, via_pallas):
        np.testing.assert_array_equal(
            np.asarray(got).astype(np.uint32).astype(np.uint64), want)


def test_jnp_and_pallas_backends_agree_mixed_engine_hints(rng):
    """A plan whose passes carry *mixed* engine hints (onehot, scatter,
    and cost-model auto) must sort identically through both single-host
    backends — hints are execution metadata, never semantics."""
    n, p = 4096, 32
    keys = rng.integers(0, 1 << p, n, dtype=np.uint64).astype(np.uint32)
    arr = jnp.asarray(keys, jnp.uint32)
    base = make_sort_plan(n, p, max_bins_log2=8)
    hints = ["scatter", "onehot", None, "scatter"]
    plan = SortPlan(n=n, p=p, passes=tuple(
        DigitPass(shift=dp.shift, bits=dp.bits, kind=dp.kind, engine=e)
        for dp, e in zip(base.passes, hints)))
    want = np.sort(keys.astype(np.uint64))
    for backend in (JnpBackend(), PallasBackend(interpret=True)):
        got = PlanExecutor(backend).run(arr, plan)
        np.testing.assert_array_equal(
            np.asarray(got).astype(np.uint32).astype(np.uint64), want,
            err_msg=str(backend))
    # pairs mode too: payload must ride identically under mixed hints
    vals = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))
    order = np.argsort(keys, kind="stable")
    for backend in (JnpBackend(), PallasBackend(interpret=True)):
        sk, sv = PlanExecutor(backend).run_pairs(arr, vals, plan)
        np.testing.assert_array_equal(np.asarray(sv),
                                      np.asarray(vals)[order],
                                      err_msg=str(backend))


@pytest.mark.parametrize("engine", ["onehot", "scatter"])
def test_engine_hinted_plans_sort_correctly(rng, engine):
    """Whole-plan engine stamps (``make_sort_plan(..., engine=)``) across
    widths, including the paper's 16-bit field under the scatter engine —
    the plan the one-hot engine could never execute in reasonable time."""
    for n, p, w in [(3000, 16, 8), (2048, 32, 11), (2048, 32, 16)]:
        if engine == "onehot" and w == 16:
            continue  # the O(n * 2**16) tile: exactly what scatter removes
        keys = rng.integers(0, 1 << p, n, dtype=np.uint64).astype(np.uint32)
        arr = jnp.asarray(keys, jnp.uint32 if p == 32 else jnp.int32)
        got = fractal_sort(arr, p,
                           plan=make_sort_plan(n, p, max_bins_log2=w,
                                               engine=engine))
        np.testing.assert_array_equal(
            np.asarray(got).astype(np.uint32).astype(np.uint64),
            np.sort(keys.astype(np.uint64)), err_msg=f"{n},{p},{w}")


def test_distributed_backend_agrees_on_single_device_mesh(rng):
    """jnp == distributed on a 1-device mesh (the in-process slice of the
    backend-equivalence matrix; the 8-device case runs in
    test_distributed.py subprocesses)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.core import distributed_fractal_sort

    mesh = make_mesh((1,), ("data",))
    # one representative plan: shard_map compile cost scales with pass
    # count, and the 8-device subprocess suite covers p=32 separately
    for p, w in [(16, None)]:
        keys = rng.integers(0, 1 << p, 2048, dtype=np.uint64).astype(np.uint32)
        dtype = jnp.uint32 if p == 32 else jnp.int32
        arr = jax.device_put(jnp.asarray(keys, dtype),
                             NamedSharding(mesh, P("data")))
        got, ov = distributed_fractal_sort(arr, mesh, "data", p,
                                           max_bins_log2=w)
        assert not bool(ov)
        want = np.asarray(fractal_sort(jnp.asarray(keys, dtype), p,
                                       max_bins_log2=w)).astype(np.uint64)
        np.testing.assert_array_equal(
            np.asarray(got).astype(np.uint64), want)


# --- pairs (key–value) mode --------------------------------------------------


def _dup_heavy(rng, dist, n, p):
    """The join/group-by hot case: most keys equal."""
    if dist == "all_equal":
        k = np.full(n, min(77, (1 << p) - 1))
    elif dist == "two_value":
        k = rng.choice([7, (1 << p) - 1], n)
    else:  # zipf
        k = np.minimum(rng.zipf(1.2, n), (1 << p) - 1)
    return k.astype(np.int32)


@pytest.mark.parametrize("n,p", [(3000, 16), (2048, 32), (1, 8), (4097, 12)])
def test_run_pairs_jnp_and_pallas_agree(rng, n, p):
    """The payload must ride every pass — including the MSD reconstruct —
    identically on both single-host backends."""
    keys = rng.integers(0, 1 << p, n, dtype=np.uint64).astype(np.uint32)
    arr = jnp.asarray(keys, jnp.uint32 if p == 32 else jnp.int32)
    vals = jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32))
    plan = make_sort_plan(n, p)
    order = np.argsort(keys, kind="stable")
    for backend in (JnpBackend(), PallasBackend(interpret=True)):
        sk, sv = PlanExecutor(backend).run_pairs(arr, vals, plan)
        np.testing.assert_array_equal(
            np.asarray(sk).astype(np.uint32), keys[order], err_msg=str(backend))
        np.testing.assert_array_equal(
            np.asarray(sv), np.asarray(vals)[order], err_msg=str(backend))


@pytest.mark.parametrize("dist", ["all_equal", "two_value", "zipf"])
def test_pairs_stable_on_duplicates(rng, dist):
    """Equal keys must keep arrival order in the payload — the property
    every query operator (join ties, group segments) leans on."""
    n, p = 4096, 16
    keys = _dup_heavy(rng, dist, n, p)
    sk, sv = fractal_sort_pairs(jnp.asarray(keys),
                                jnp.arange(n, dtype=jnp.int32), p)
    np.testing.assert_array_equal(np.asarray(sv),
                                  np.argsort(keys, kind="stable"))
    np.testing.assert_array_equal(np.asarray(sk), np.sort(keys))


# --- argsort stability on duplicate-heavy inputs, all three backends ---------


@pytest.mark.parametrize("dist", ["all_equal", "two_value", "zipf"])
@pytest.mark.parametrize("backend", ["jnp", "pallas", "distributed"])
def test_argsort_duplicate_stability_across_backends(rng, dist, backend):
    """Regression (satellite of the query subsystem): duplicates are the
    join/group-by hot case, and only the jnp path was property-tested for
    stability.  The permutation must equal numpy's stable argsort on
    every backend."""
    n, p = 2048, 16
    keys = _dup_heavy(rng, dist, n, p)
    want = np.argsort(keys, kind="stable")
    if backend == "jnp":
        perm = fractal_argsort(jnp.asarray(keys), p)
    elif backend == "pallas":
        plan = make_sort_plan(n, p)
        perm = PlanExecutor(PallasBackend(interpret=True)).run_argsort(
            jnp.asarray(keys), plan)
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_mesh
        from repro.core import distributed_fractal_argsort

        mesh = make_mesh((1,), ("data",))
        arr = jax.device_put(jnp.asarray(keys),
                             NamedSharding(mesh, P("data")))
        perm, ov = distributed_fractal_argsort(arr, mesh, "data", p)
        assert not bool(ov)
    np.testing.assert_array_equal(np.asarray(perm), want, err_msg=dist)


# --- the TPU placement: one sort keyed by rank --------------------------------


class _SortPlacementBackend(JnpBackend):
    """JnpBackend placing by :func:`sort_placement` on any platform (the
    executor picks it only where the program is lowered for a TPU)."""

    def scatter(self, rank, *arrays):
        return sort_placement(rank, *arrays)


def _placement_case(rng, mode, n):
    """``(fn, args)`` of one placement case; ``fn(executor, *args)``."""
    if mode.startswith("place:"):
        rank = jnp.asarray(rng.permutation(n).astype(np.int32))
        arrays = [jnp.asarray(rng.integers(0, 1 << 32, n, dtype=np.uint64)
                              .astype(np.uint32).view(dt))
                  for dt in mode[len("place:"):].split(",")]
        return (lambda ex, *a: ex.backend.scatter(*a)), (rank, *arrays)
    # duplicate-heavy 12-bit keys over 3 passes (two LSD, the MSD), so
    # every pass's ranks order many equal digits by arrival
    p = 12
    plan = make_sort_plan(n, p)
    keys = rng.choice([3, 0x0F3, 0x5A0, 0xFFF], n).astype(np.uint32)
    if mode == "run_grouped_trailing":
        t = plan.trailing_bits
        grouped = np.sort(keys)
        counts = np.bincount(grouped >> t, minlength=1 << plan.depth)
        entries = grouped & np.uint32((1 << t) - 1)
        for s, c in zip(np.cumsum(counts) - counts, counts):
            entries[s:s + c] = rng.permutation(entries[s:s + c])
        return (lambda ex, e, c: ex.run_grouped_trailing(e, c, plan),
                (jnp.asarray(entries), jnp.asarray(counts.astype(np.int32))))
    k = jnp.asarray(keys)
    if mode == "run_pairs":
        vals = (jnp.asarray(rng.integers(0, 1 << 30, n).astype(np.int32)),
                jnp.asarray(keys ^ np.uint32(0xDEADBEEF)))
        return (lambda ex, k, v: ex.run_pairs(k, v, plan)), (k, vals)
    if mode == "run_segmented_argsort":
        seg = min(3, (n & -n).bit_length() - 1)
        return (lambda ex, k: ex.run_segmented_argsort(k, plan, seg)), (k,)
    return (lambda ex, k: getattr(ex, mode)(k, plan)), (k,)


@pytest.mark.parametrize("n", [1, 1000, 4099])
@pytest.mark.parametrize("mode", [
    "place:int32", "place:uint32", "place:uint32,int32", "run",
    "run_pairs", "run_argsort", "run_segmented_argsort",
    "run_grouped_trailing"])
def test_sort_placement_matches_scatter(rng, mode, n):
    """The TPU's placement (one sort of the ranks and every carried
    array) gives, bit for bit, what the scatter gives: called directly on
    a permutation, and through every executor mode that places with
    ranks, on duplicate-heavy keys, so the ranks carry the stability."""
    fn, args = _placement_case(rng, mode, n)
    want = jax.tree_util.tree_leaves(fn(PlanExecutor(JnpBackend()), *args))
    got = jax.tree_util.tree_leaves(
        fn(PlanExecutor(_SortPlacementBackend()), *args))
    assert len(got) == len(want)
    for w, g in zip(want, got):
        assert w.dtype == g.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=mode)


# --- segment-aware grouped-trailing mode -------------------------------------


def test_grouped_trailing_equals_per_segment_oracle(rng):
    """run_grouped_trailing == numpy sorting each segment's trailing bits
    independently (segments never mix)."""
    depth, t, n = 4, 8, 4096
    p = depth + t
    plan = make_sort_plan(n, p)
    assert plan.depth == depth and plan.trailing_bits == t
    assert plan.supports_grouped_trailing
    keys = rng.integers(0, 1 << p, n).astype(np.uint32)
    grouped = np.sort(keys)  # grouped by prefix (and conveniently sorted)
    counts = np.bincount(grouped >> t, minlength=1 << depth).astype(np.int32)
    # scramble trailing bits within segments, keep segment grouping
    entries = grouped & ((1 << t) - 1)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for s, c in zip(starts, counts):
        entries[s:s + c] = rng.permutation(entries[s:s + c])
    out = PlanExecutor(JnpBackend()).run_grouped_trailing(
        jnp.asarray(entries, jnp.uint32), jnp.asarray(counts), plan)
    np.testing.assert_array_equal(np.asarray(out).astype(np.uint64),
                                  np.sort(keys.astype(np.uint64)))


@pytest.mark.parametrize("num_batches", [1, 3, 8])
@pytest.mark.parametrize("dist", ["uniform", "all_equal", "two_hot"])
def test_batched_grouped_trailing_distributions(rng, num_batches, dist):
    n, p = 4096, 24
    if dist == "uniform":
        keys = rng.integers(0, 1 << p, n)
    elif dist == "all_equal":
        keys = np.full(n, 12345)
    else:
        keys = rng.choice([5, (1 << p) - 3], n)
    arr = jnp.asarray(keys.astype(np.int32))
    direct = fractal_sort(arr, p)
    streamed, _ = fractal_sort_batched(arr, p, num_batches)
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(direct))


def test_batched_wide_plan_falls_back_to_full_plan(rng):
    """The paper's 16b+16b p=32 plan exceeds the grouped-trailing table
    cap; the streaming path must detect that and still sort correctly."""
    n = 2048
    plan = make_sort_plan(n, 32, max_bins_log2=16)
    assert not plan.supports_grouped_trailing
    keys = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
    streamed, _ = fractal_sort_batched(jnp.asarray(keys, jnp.uint32), 32, 4,
                                       max_bins_log2=16)
    np.testing.assert_array_equal(np.asarray(streamed), np.sort(keys))


# --- distributed overflow resets between runs --------------------------------


def test_distributed_overflow_resets_between_runs(rng):
    """Regression: ``DistributedBackend.overflow`` accumulated across runs
    when an executor was reused — a second, clean run reported the first
    run's overflow forever.  ``begin_run`` must reset it: run 1 (64 keys
    through capacity-32 buckets on one device) overflows, run 2 (16 keys)
    must not."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.core import DistributedBackend

    mesh = make_mesh((1,), ("data",))
    n1, n2, cap = 64, 16, 32
    plan1, plan2 = make_sort_plan(n1, 8), make_sort_plan(n2, 8)

    def body(a, b):
        backend = DistributedBackend(axis="data", capacity=cap, batch=32)
        ex = PlanExecutor(backend)
        out1 = ex.run(a, plan1)
        ov1 = backend.overflow
        out2 = ex.run(b, plan2)
        ov2 = backend.overflow
        return out1, ov1, out2, ov2

    a = jax.device_put(jnp.asarray(rng.integers(0, 256, n1), jnp.int32),
                       NamedSharding(mesh, P("data")))
    b = jax.device_put(jnp.asarray(rng.integers(0, 256, n2), jnp.int32),
                       NamedSharding(mesh, P("data")))
    out1, ov1, out2, ov2 = jax.shard_map(
        body, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P("data"), P(), P("data"), P()))(a, b)
    # on one device every key targets bucket 0: run 1 overflows (64 > 32,
    # flagged + dropped), run 2 fits and must report clean
    assert bool(ov1)
    assert not bool(ov2), "overflow leaked across executor runs"
    np.testing.assert_array_equal(np.asarray(out2),
                                  np.sort(np.asarray(b)))


# --- empty-input guard -------------------------------------------------------


def test_empty_input_regression():
    """fractal_sort(jnp.array([]), p=16) used to raise (fractal_rank
    indexed prefix[0] unconditionally); the executor guards n == 0."""
    for dtype, p in [(jnp.int32, 16), (jnp.uint32, 32), (jnp.int32, 8)]:
        out = fractal_sort(jnp.array([], dtype=dtype), p)
        assert out.shape == (0,)
    perm = fractal_argsort(jnp.array([], dtype=jnp.int32), 8)
    assert perm.shape == (0,) and perm.dtype == jnp.int32
    rank, counts, carry = fractal_rank(jnp.array([], dtype=jnp.int32), 16)
    assert rank.shape == (0,)
    np.testing.assert_array_equal(np.asarray(counts), np.zeros(16))
    np.testing.assert_array_equal(np.asarray(carry), np.zeros(16))


# --- plan execution hints ----------------------------------------------------


def test_plan_execution_hints():
    from repro.core import rank_chunk_len

    plan = make_sort_plan(1 << 15, 32)
    for dp in plan.passes:
        assert dp.rank_batch(1024) == rank_chunk_len(dp.n_bins, 1024)
        assert dp.rank_batch(1024) * dp.n_bins <= 1 << 21
    assert plan.supports_grouped_trailing
    wide = make_sort_plan(1 << 15, 32, max_bins_log2=16)
    assert wide.grouped_table_log2 > 20
    assert not wide.supports_grouped_trailing
    # one-pass plans have no trailing bits to group
    single = make_sort_plan(1 << 20, 16, max_bins_log2=16)
    assert not single.supports_grouped_trailing
    # the gate is n-aware: a wide-ish plan over a small input would build
    # a per-segment table dwarfing the keys — fall back instead
    small = make_sort_plan(2048, 24, max_bins_log2=10)
    assert small.grouped_table_log2 > 15
    assert not small.supports_grouped_trailing
