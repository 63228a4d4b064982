"""Core fractal sort: correctness, stability, streaming, compression."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    bit_reverse,
    build_histogram,
    fractal_argsort,
    fractal_sort,
    fractal_sort_batched,
    fractal_sort_pairs,
    fractal_sort_stats,
    get_index,
    get_item,
    histogram_nbytes,
    make_sort_plan,
    merge_histograms,
    reconstruct,
    taper_levels,
    tapered_bits,
    tapered_dtype,
    trie_depth,
)


@pytest.mark.parametrize("n,p", [
    (1000, 8), (4096, 16), (1 << 14, 16), (3000, 12), (5000, 24),
    (2048, 32), (17, 4), (1, 8),
])
def test_sort_matches_numpy(rng, n, p):
    hi = 1 << min(p, 31)
    keys = rng.integers(0, hi, n).astype(np.int64)
    if p == 32:
        keys = rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32)
        arr = jnp.asarray(keys, jnp.uint32)
    else:
        arr = jnp.asarray(keys, jnp.int32)
    out = np.asarray(fractal_sort(arr, p)).astype(np.uint64)
    assert np.array_equal(out, np.sort(keys.astype(np.uint64)))


@pytest.mark.parametrize("dist", ["uniform", "all_equal", "sorted",
                                  "reversed", "zipf", "two_values"])
def test_sort_distribution_independence(rng, dist):
    """The paper's pitch: no distribution-dependent pre-processing."""
    n, p = 4096, 16
    if dist == "uniform":
        keys = rng.integers(0, 1 << p, n)
    elif dist == "all_equal":
        keys = np.full(n, 1234)
    elif dist == "sorted":
        keys = np.sort(rng.integers(0, 1 << p, n))
    elif dist == "reversed":
        keys = np.sort(rng.integers(0, 1 << p, n))[::-1].copy()
    elif dist == "zipf":
        keys = np.clip(rng.zipf(1.2, n), 0, (1 << p) - 1)
    else:
        keys = rng.choice([7, 65535], n)
    arr = jnp.asarray(keys.astype(np.int32))
    out = np.asarray(fractal_sort(arr, p))
    assert np.array_equal(out, np.sort(keys))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, (1 << 16) - 1), min_size=1, max_size=500),
       st.sampled_from([8, 12, 16]))
def test_sort_property(keys, p):
    keys = [k & ((1 << p) - 1) for k in keys]
    arr = jnp.asarray(np.asarray(keys, np.int32))
    out = np.asarray(fractal_sort(arr, p))
    assert np.array_equal(out, np.sort(keys))


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 2000), st.integers(2, 64))
def test_argsort_stable_property(n, e):
    rng = np.random.default_rng(n * 7 + e)
    p = int(np.ceil(np.log2(e)))
    keys = rng.integers(0, e, n).astype(np.int32)
    perm = np.asarray(fractal_argsort(jnp.asarray(keys), max(p, 1)))
    assert sorted(perm.tolist()) == list(range(n))  # permutation
    s = keys[perm]
    assert np.all(np.diff(s) >= 0)  # sorted
    for i in range(n - 1):  # stability
        if s[i] == s[i + 1]:
            assert perm[i] < perm[i + 1]


def test_batched_streaming_equals_direct(rng):
    keys = jnp.asarray(rng.integers(0, 1 << 16, 8192), jnp.int32)
    direct = fractal_sort(keys, 16)
    for b in (2, 4, 8):
        streamed, hists = fractal_sort_batched(keys, 16, b)
        assert bool((streamed == direct).all())
        assert len(hists) == b
        merged = functools.reduce(merge_histograms, hists)
        full = build_histogram(keys, 16, hists[0].depth)
        assert all(bool((a == b_).all())
                   for a, b_ in zip(merged.levels, full.levels))


def test_reconstruct_bit_reverse_equivalence(rng):
    """MSB-first implicit layout == paper's LSB-first tree-walk order after
    BitReverse (DESIGN.md §2 relabeling claim)."""
    n, l_n = 2048, 8
    keys = rng.integers(0, 1 << l_n, n).astype(np.int32)
    counts_msb = np.bincount(keys, minlength=1 << l_n).astype(np.int32)
    # counts stored in LSB-first tree-walk order
    rev = np.asarray(bit_reverse(jnp.arange(1 << l_n), l_n))
    counts_lsb = counts_msb[rev]
    out = reconstruct(jnp.asarray(counts_msb), jnp.zeros((n,), jnp.uint32),
                      l_n, l_n)
    out_lsb = reconstruct(jnp.asarray(counts_lsb), jnp.zeros((n,), jnp.uint32),
                          l_n, l_n, lsb_tree_order=True)
    assert np.array_equal(np.sort(np.asarray(out_lsb)), np.asarray(out))


def test_trie_queries(rng):
    keys = jnp.asarray(rng.integers(0, 1 << 16, 4096), jnp.int32)
    h = build_histogram(keys, 16, 10)
    srt = np.sort(np.asarray((keys.astype(jnp.uint32) >> 6).astype(jnp.int32)))
    idx = jnp.asarray([0, 17, 4095])
    assert np.array_equal(np.asarray(get_item(h, idx)), srt[np.asarray(idx)])
    v = int(srt[100])
    assert int(get_index(h, jnp.asarray(v))) == int(np.argmax(srt == v))


def test_counter_width_tapering(rng):
    """Tapered storage must be substantially smaller and lossless when
    balanced; saturation flag must fire under adversarial skew."""
    keys = jnp.asarray(rng.integers(0, 1 << 16, 8192), jnp.int32)
    h = build_histogram(keys, 16, 10)
    tl, sat = taper_levels(h, n_hint=8192)
    assert not bool(sat)
    for lvl, t in zip(h.levels, tl):
        assert np.array_equal(np.asarray(lvl), np.asarray(t).astype(np.int64))
    assert histogram_nbytes(h, True, 8192) < histogram_nbytes(h, False, 8192) / 2
    # adversarial: every key identical -> deep counters overflow taper width
    skew = jnp.zeros((8192,), jnp.int32)
    hs = build_histogram(skew, 16, 10)
    _, sat = taper_levels(hs, n_hint=8192)
    assert bool(sat)


def test_tapered_bits_monotone():
    widths = [tapered_bits(l, 16) for l in range(17)]
    assert widths == sorted(widths, reverse=True)
    assert tapered_dtype(0, 20) == jnp.uint32
    assert tapered_dtype(18, 20) == jnp.uint8


def test_sort_stats_bandwidth_model():
    """n >= 2**p: zero trailing payload -> ~2 key-widths of traffic/key
    (one read + one write), the paper's headline compression regime."""
    st16 = fractal_sort_stats(1 << 20, 16)
    assert st16.l_n == 16 and st16.passes == 1
    assert st16.bytes_per_key == pytest.approx(4.0)  # 2B read + 2B write
    st32 = fractal_sort_stats(1 << 20, 32)
    assert st32.passes == 2
    # radix comparison: fractal must move fewer bytes than 4-pass radix
    from repro.core import radix_sort_stats
    assert st32.bytes_total < radix_sort_stats(1 << 20, 32).bytes_total


def test_entry_points_accept_pinned_plans(rng):
    """plan= must reach every entry point unchanged (zero API breakage:
    the old signatures still work, the new static arg pins execution)."""
    n, p = 2048, 16
    keys = rng.integers(0, 1 << p, n).astype(np.int32)
    arr = jnp.asarray(keys)
    plan = make_sort_plan(n, p, max_bins_log2=8, engine="scatter")
    np.testing.assert_array_equal(
        np.asarray(fractal_sort(arr, p, plan=plan)), np.sort(keys))
    perm = fractal_argsort(arr, p, plan=plan)
    np.testing.assert_array_equal(np.asarray(perm),
                                  np.argsort(keys, kind="stable"))
    vals = jnp.arange(n, dtype=jnp.int32)
    sk, sv = fractal_sort_pairs(arr, vals, p, plan=plan)
    np.testing.assert_array_equal(np.asarray(sv),
                                  np.argsort(keys, kind="stable"))
    streamed, _ = fractal_sort_batched(arr, p, 4, plan=plan)
    np.testing.assert_array_equal(np.asarray(streamed), np.sort(keys))
    with pytest.raises(AssertionError):
        fractal_sort(arr, 12, plan=plan)  # plan/p mismatch is loud
