"""SortPlan planner: construction invariants, oracle sorts across
precisions and adversarial distributions, argsort stability, batched
merge telescoping, and per-pass traffic accounting."""

import functools
import importlib

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    DEFAULT_MAX_BINS_LOG2,
    ONEHOT_MAX_BITS,
    JnpBackend,
    build_histogram,
    default_engine,
    fractal_argsort,
    fractal_sort,
    fractal_sort_batched,
    fractal_rank_serial,
    fractal_sort_stats,
    make_sort_plan,
    merge_histograms,
    scatter_tile_len,
)
from repro.core.fractal_sort import _resolve_plan
from repro.query.operators import _resolve_plans, active_words


# --- plan construction -------------------------------------------------------


@pytest.mark.parametrize("n,p", [
    (1, 8), (17, 4), (64, 16), (1000, 8), (4096, 16), (1 << 14, 16),
    (5000, 24), (1 << 15, 32), (1 << 20, 32),
])
def test_plan_covers_bits_contiguously(n, p):
    plan = make_sort_plan(n, p)
    assert plan.n == n and plan.p == p
    shift = 0
    for dp in plan.passes:
        assert dp.shift == shift, "passes must tile the key LSD->MSD"
        assert dp.bits >= 1
        shift += dp.bits
    assert shift == p, "passes must cover every key bit exactly once"
    assert plan.passes[-1].kind == "msd"
    assert all(dp.kind == "lsd" for dp in plan.passes[:-1])


@pytest.mark.parametrize("w_max", [4, 6, 8, 11, 16])
def test_plan_respects_bin_cap(w_max):
    for n, p in [(1 << 10, 16), (1 << 15, 32), (100, 24)]:
        plan = make_sort_plan(n, p, max_bins_log2=w_max)
        assert all(dp.bits <= w_max for dp in plan.passes), plan
        assert plan.depth <= w_max


def test_plan_tiny_inputs_bound_bins_by_data_scale():
    """n=64, p=16 must not get a 2**10-bin trailing pass (the seed's
    pathological one-hot tile); digits stay near log2(n)."""
    plan = make_sort_plan(64, 16)
    assert all(dp.n_bins <= 64 for dp in plan.passes), plan
    plan1 = make_sort_plan(1, 8)
    assert all(dp.n_bins <= 16 for dp in plan1.passes), plan1


def test_plan_p0_identity_and_no_degenerate_passes():
    """p=0 (zero-width keys — the external sort's exhausted-recursion
    case) is the empty identity plan; no plan ever emits a 1-bin
    (zero-width) pass."""
    from repro.core import PlanExecutor, JnpBackend, fractal_argsort

    plan = make_sort_plan(100, 0)
    assert plan.passes == ()
    assert plan.depth == 0 and plan.trailing_bits == 0
    assert not plan.supports_grouped_trailing
    assert plan.describe() == "identity"
    keys = jnp.zeros((17,), jnp.int32)
    ex = PlanExecutor(JnpBackend())
    assert np.array_equal(np.asarray(ex.run(keys, plan)), np.zeros(17))
    sk, vals = ex.run_pairs(keys, jnp.arange(17, dtype=jnp.int32), plan)
    assert np.array_equal(np.asarray(vals), np.arange(17))
    assert np.array_equal(np.asarray(fractal_argsort(keys, p=0)),
                          np.arange(17))
    for n in (1, 64, 5000):
        for p in range(1, 33):
            assert all(dp.bits >= 1 for dp in make_sort_plan(n, p).passes)


@pytest.mark.parametrize("bits", range(1, 17))
def test_default_engine_by_width(bits, monkeypatch):
    """A pass with no engine hint ranks one-hot at 1-5 bits and through
    the scatter engine at 6-16, the table the analytic cost model gave at
    every n.  On each side of the threshold the picked engine ranks
    exactly as the serial-scan oracle does."""
    want = "onehot" if bits <= 5 else "scatter"
    assert ONEHOT_MAX_BITS == 5
    assert default_engine(bits) == want
    # scatter tiles grow with the digit (the one-hot chunk hint shrinks)
    assert scatter_tile_len(1 << bits) >= scatter_tile_len(1 << 4)
    if bits not in (ONEHOT_MAX_BITS, ONEHOT_MAX_BITS + 1):
        return
    fs = importlib.import_module("repro.core.fractal_sort")
    picked = []

    def spy(engine):
        def ranked(*args, batch, **kw):
            picked.append((engine, batch))
            return fs.RANK_ENGINES[engine](*args, batch=batch, **kw)
        return ranked

    monkeypatch.setattr(fs, "rank_engine", spy)
    n, n_bins = 700, 1 << bits
    digit = jnp.asarray(np.random.default_rng(bits).integers(0, n_bins, n),
                        jnp.int32)
    rank, counts, _ = JnpBackend().rank(digit, n_bins, engine=None)
    want_rank, want_counts, _ = fractal_rank_serial(digit, n_bins)
    # a scatter pass re-derives its tile from the digit width
    tile = scatter_tile_len(n_bins, 1024) if want == "scatter" else 1024
    assert picked == [(want, tile)]
    np.testing.assert_array_equal(np.asarray(rank), np.asarray(want_rank))
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.asarray(want_counts))


# (n, key bits, default plan) of every sort the benchmark cells run: bulk
# 2^27 keys at p=16; Q18's LINEITEM; Q3's LINEITEM and first-join output
# on orderkey, its ORDERS and CUSTOMER on custkey; Q1's 3-bit GROUP BY
# key over the rows its filter keeps; and the words of Q3's 41-bit GROUP
# BY key and 78-bit top-k key as active_words splits them.
_W32 = "+".join(["4b"] * 8)
_CELL_SORTS = {
    "bulk": (1 << 27, 16, ("4b+4b+4b+4b",)),
    "q18.lineitem": (59_986_052, 26, ("4b+4b+4b+4b+3b+3b+4b",)),
    "q3.lineitem": (32_336_621, 26, ("4b+4b+4b+4b+3b+3b+4b",)),
    "q3.first_join": (1_460_000, 26, ("4b+4b+4b+4b+3b+3b+4b",)),
    "q3.orders": (7_290_000, 21, ("4b+4b+3b+3b+3b+4b",)),
    "q3.customer": (300_000, 21, ("4b+4b+3b+3b+3b+4b",)),
    "q1.flags": (59_142_284, 3, ("3b",)),
    "q3.group_by": (300_000, 41, (_W32, "3b+2b+4b")),
    "q3.top_k": (114_000, 78, (_W32, _W32, "4b+3b+3b+4b")),
}


@pytest.mark.parametrize("cell", sorted(_CELL_SORTS))
def test_cell_default_plans(cell):
    """Every cell's sort resolves to make_sort_plan's default, through
    the entry points and the query operators alike, and ranks each pass
    one-hot.  Plan objects only: no arrays."""
    n, bits, want = _CELL_SORTS[cell]
    active = active_words(bits)
    plans = _resolve_plans(n, active, None)
    assert plans == tuple(make_sort_plan(n, eff) for _, eff in active)
    assert tuple(p.describe() for p in plans) == want
    for (_, eff), plan in zip(active, plans):
        assert _resolve_plan(n, eff, None, None, None) == plan
        assert all(dp.engine is None and default_engine(dp.bits) == "onehot"
                   for dp in plan.passes)


def test_plan_explicit_ln_wins_over_cap():
    """A caller-supplied trie depth is honored, not silently clamped to
    the bin cap; only the LSD digits stay capped."""
    plan = make_sort_plan(1 << 14, 16, l_n=12, max_bins_log2=4)
    assert plan.depth == 12
    assert all(dp.bits <= 4 for dp in plan.passes[:-1])
    out_keys = np.random.default_rng(7).integers(0, 1 << 16, 2048)
    got = fractal_sort(jnp.asarray(out_keys, jnp.int32), 16, l_n=12)
    assert np.array_equal(np.asarray(got), np.sort(out_keys))


def test_plan_paper_regime_single_pass():
    """n >= 2**p with a 16-bit budget: one zero-payload fractal pass."""
    plan = make_sort_plan(1 << 20, 16, max_bins_log2=16)
    assert plan.num_passes == 1
    assert plan.trailing_bits == 0
    assert plan.depth == 16


# --- oracle sorts ------------------------------------------------------------


def _keys_for(dist: str, n: int, p: int, rng):
    hi = 1 << p
    if dist == "uniform":
        k = rng.integers(0, hi, n, dtype=np.uint64)
    elif dist == "all_equal":
        k = np.full(n, (hi - 1) // 3, np.uint64)
    elif dist == "reversed":
        k = np.sort(rng.integers(0, hi, n, dtype=np.uint64))[::-1].copy()
    else:  # two-hot skew: two values, heavily imbalanced
        a, b = 1, hi - 2
        k = np.where(rng.random(n) < 0.95, a, b).astype(np.uint64)
    return k


@pytest.mark.parametrize("p", [8, 12, 16, 24, 32])
@pytest.mark.parametrize("dist", ["uniform", "all_equal", "reversed",
                                  "two_hot"])
def test_sort_oracle_precisions_and_distributions(rng, p, dist):
    n = 4096
    keys = _keys_for(dist, n, p, rng)
    dtype = jnp.uint32 if p == 32 else jnp.int32
    arr = jnp.asarray(keys.astype(np.uint32), dtype)
    out = np.asarray(fractal_sort(arr, p)).astype(np.uint64)
    assert np.array_equal(out, np.sort(keys)), (p, dist)


@pytest.mark.parametrize("w_max", [4, 8, 11])
def test_sort_oracle_across_bin_caps(rng, w_max):
    keys = rng.integers(0, 1 << 32, 3000, dtype=np.uint64).astype(np.uint32)
    out = fractal_sort(jnp.asarray(keys, jnp.uint32), 32,
                       max_bins_log2=w_max)
    assert np.array_equal(np.asarray(out), np.sort(keys))


# --- argsort stability under the plan ---------------------------------------


@pytest.mark.parametrize("p,e", [(4, 16), (7, 100), (16, 40000), (20, 9)])
def test_argsort_stable_under_plan(rng, p, e):
    n = 3000
    keys = rng.integers(0, min(e, 1 << p), n).astype(np.int32)
    perm = np.asarray(fractal_argsort(jnp.asarray(keys), p))
    assert sorted(perm.tolist()) == list(range(n))
    s = keys[perm]
    assert np.all(np.diff(s) >= 0)
    same = s[:-1] == s[1:]
    assert np.all(perm[:-1][same] < perm[1:][same]), "stability"


# --- batched streaming under the plan ---------------------------------------


@pytest.mark.parametrize("p", [16, 32])
def test_batched_merge_telescopes_under_plan(rng, p):
    n = 8192
    if p == 32:
        keys = jnp.asarray(
            rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32),
            jnp.uint32)
    else:
        keys = jnp.asarray(rng.integers(0, 1 << p, n), jnp.int32)
    direct = fractal_sort(keys, p)
    for b in (3, 8):
        streamed, hists = fractal_sort_batched(keys, p, b)
        assert bool((streamed == direct).all()), (p, b)
        assert len(hists) == b
        merged = functools.reduce(merge_histograms, hists)
        full = build_histogram(keys, p, hists[0].depth)
        assert all(bool((x == y).all())
                   for x, y in zip(merged.levels, full.levels))
        # the streamed histograms live at the plan's MSD depth
        assert hists[0].depth == make_sort_plan(n, p).depth


# --- per-pass traffic accounting --------------------------------------------


def test_stats_per_pass_sums_to_totals():
    for n, p in [(1 << 20, 16), (1 << 20, 32), (4096, 24)]:
        for plan in (None, make_sort_plan(n, p),
                     make_sort_plan(n, p, max_bins_log2=11)):
            st = fractal_sort_stats(n, p, plan=plan)
            assert st.passes == len(st.pass_stats)
            assert st.bytes_read == sum(ps.bytes_read for ps in st.pass_stats)
            assert st.bytes_written == sum(ps.bytes_written
                                           for ps in st.pass_stats)


def test_stats_paper_plan_headline_unchanged():
    """Default (paper) plan keeps the n >= 2**p headline: one pass, zero
    payload, ~2 key-widths of traffic per key."""
    st = fractal_sort_stats(1 << 20, 16)
    assert st.passes == 1 and st.l_n == 16
    assert st.bytes_per_key == pytest.approx(4.0)
    (ps,) = st.pass_stats
    assert ps.kind == "msd" and ps.shift == 0


def test_stats_execution_plan_traffic_scales_with_passes():
    """Narrower digits -> more passes -> more key traffic; the analytic
    model must reflect the trade the planner makes."""
    wide = fractal_sort_stats(1 << 20, 32, plan=make_sort_plan(
        1 << 20, 32, max_bins_log2=16))
    narrow = fractal_sort_stats(1 << 20, 32, plan=make_sort_plan(
        1 << 20, 32, max_bins_log2=8))
    assert narrow.passes > wide.passes
    assert narrow.bytes_total > wide.bytes_total
    # but both beat the classic radix baseline that moves full keys +
    # index payloads every pass
    from repro.core import radix_sort_stats
    assert narrow.bytes_total < radix_sort_stats(
        1 << 20, 32, with_index=True).bytes_total


def test_default_bin_cap_is_bounded():
    assert 4 <= DEFAULT_MAX_BINS_LOG2 <= 11
