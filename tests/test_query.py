"""Query subsystem: codecs round-trip and preserve order for every
supported dtype; every operator matches a pure-XLA (``jnp.sort`` /
``jnp.lexsort``) oracle on property-style inputs — multi-column asc/desc
mixes, negative ints, NaN-free floats, duplicate-heavy join keys — and
``order_by`` is stable."""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.query import (
    BoolCodec,
    ColumnSpec,
    CompositeCodec,
    Float32Codec,
    Float64Codec,
    IntCodec,
    Table,
    UIntCodec,
    distinct,
    group_by,
    infer_codec,
    order_by,
    sort_merge_join,
    sort_rowids,
    top_k,
    word_widths,
)

# --- codecs -------------------------------------------------------------------

CODEC_CASES = {
    "bool": (BoolCodec(), lambda rng, n: rng.random(n) < 0.5),
    "int8": (IntCodec(8), lambda rng, n:
             rng.integers(-128, 128, n).astype(np.int32)),
    "int16": (IntCodec(16), lambda rng, n:
              rng.integers(-(1 << 15), 1 << 15, n).astype(np.int32)),
    "int32": (IntCodec(32), lambda rng, n:
              rng.integers(-(1 << 31), 1 << 31, n, dtype=np.int64)
              .astype(np.int32)),
    "uint16": (UIntCodec(16), lambda rng, n:
               rng.integers(0, 1 << 16, n).astype(np.uint32)),
    "uint32": (UIntCodec(32), lambda rng, n:
               rng.integers(0, 1 << 32, n, dtype=np.uint64)
               .astype(np.uint32)),
    "float32": (Float32Codec(), lambda rng, n:
                np.concatenate([
                    (rng.standard_normal(n - 6) * 10.0 ** rng.integers(
                        -20, 20, n - 6)).astype(np.float32),
                    np.asarray([0.0, -0.0, np.inf, -np.inf,
                                np.float32(1e-45), np.float32(3.4e38)],
                               np.float32)])),
    "float64": (Float64Codec(), lambda rng, n:
                np.concatenate([
                    rng.standard_normal(n - 4) * 10.0 ** rng.integers(
                        -200, 200, n - 4),
                    np.asarray([0.0, -0.0, np.inf, -np.inf])])),
}


def _code_as_bigint(codec, words):
    """Collapse the (n, W) uint32 words into arbitrary-precision ints so
    numeric comparison of codes is exact for any width."""
    w = np.asarray(words).astype(object)
    out = np.zeros(w.shape[0], object)
    for j, bits in enumerate(word_widths(codec.bits)):
        out = (out * (1 << bits)) + w[:, j]
    return out


@pytest.mark.parametrize("name", sorted(CODEC_CASES))
def test_codec_roundtrip(rng, name):
    codec, gen = CODEC_CASES[name]
    x = gen(rng, 512)
    words = codec.encode(x)
    assert words.shape == (512, codec.num_words)
    assert np.asarray(words).dtype == np.uint32
    back = np.asarray(codec.decode(words))
    assert np.array_equal(back, np.asarray(x)), name
    if back.dtype.kind == "f":  # ±0.0 must round-trip bitwise
        assert np.array_equal(np.signbit(back), np.signbit(np.asarray(x)))


@pytest.mark.parametrize("name", sorted(CODEC_CASES))
def test_codec_preserves_order(rng, name):
    codec, gen = CODEC_CASES[name]
    x = gen(rng, 512)
    code = _code_as_bigint(codec, codec.encode(x))
    xs = np.asarray(x)
    for _ in range(300):
        i, j = rng.integers(0, len(xs), 2)
        if xs[i] < xs[j]:
            assert code[i] < code[j], (name, xs[i], xs[j])
        elif xs[i] > xs[j]:
            assert code[i] > code[j], (name, xs[i], xs[j])


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(-(1 << 31), (1 << 31) - 1), min_size=1,
                max_size=200))
def test_int_codec_property(vals):
    x = np.asarray(vals, np.int32)
    codec = IntCodec(32)
    code = np.asarray(codec.encode(x))[:, 0]
    assert np.array_equal(np.asarray(codec.decode(codec.encode(x))), x)
    assert np.array_equal(np.argsort(code, kind="stable"),
                          np.argsort(x, kind="stable"))


def test_word_widths():
    assert word_widths(1) == (1,)
    assert word_widths(32) == (32,)
    assert word_widths(33) == (32, 1)
    assert word_widths(64) == (32, 32)
    assert word_widths(65) == (32, 32, 1)
    for codec, w in [(BoolCodec(), 1), (IntCodec(9), 1),
                     (Float64Codec(), 2)]:
        assert codec.num_words == w


def test_composite_roundtrip_and_order(rng):
    n = 400
    a = rng.integers(-50, 50, n).astype(np.int32)
    b = (rng.standard_normal(n)).astype(np.float32)
    c = rng.random(n) < 0.5
    codec = CompositeCodec([
        ColumnSpec(IntCodec(8), ascending=True),
        ColumnSpec(Float32Codec(), ascending=False),
        ColumnSpec(BoolCodec(), ascending=True),
    ])
    assert codec.bits == 8 + 32 + 1
    words = codec.encode([a, b, c])
    assert words.shape == (n, 2)  # 41 bits -> two words
    da, db, dc = codec.decode(words)
    assert np.array_equal(np.asarray(da), a)
    assert np.array_equal(np.asarray(db), b)
    assert np.array_equal(np.asarray(dc), c)
    # code order == (a asc, b desc, c asc) lexicographic order
    code = _code_as_bigint(codec, words)
    want = np.lexsort((c, -b, a))
    got = np.argsort(code, kind="stable")
    key = np.stack([a, -b, c], axis=1)
    assert np.array_equal(key[got], key[want])


# --- operators vs pure-XLA oracles --------------------------------------------


def _mk_table(rng, n, key_space):
    return Table({
        "k": rng.integers(0, key_space, n).astype(np.int32),
        "f": (rng.standard_normal(n) * 100).astype(np.float32),
        "row": np.arange(n, dtype=np.int32),
    })


@pytest.mark.parametrize("dist", ["uniform", "duplicate_heavy", "all_equal"])
def test_order_by_matches_lexsort_oracle(rng, dist):
    n = 2048
    space = {"uniform": 1 << 30, "duplicate_heavy": 7, "all_equal": 1}[dist]
    t = _mk_table(rng, n, space)
    k, f = np.asarray(t.column("k")), np.asarray(t.column("f"))
    out = order_by(t, [("k", "asc"), ("f", "desc")]).to_numpy()
    perm = np.asarray(jnp.lexsort((-t.column("f"), t.column("k"))))
    assert np.array_equal(out["k"], k[perm])
    assert np.array_equal(out["f"], f[perm])


def test_order_by_is_stable(rng):
    n = 3000
    k = rng.integers(0, 5, n).astype(np.int32)  # heavy duplicates
    t = Table({"k": k, "row": np.arange(n, dtype=np.int32)})
    out = order_by(t, "k").to_numpy()
    assert np.array_equal(out["row"], np.argsort(k, kind="stable"))
    # descending must also keep arrival order within equal keys
    out_d = order_by(t, [("k", "desc")]).to_numpy()
    assert np.array_equal(out_d["row"],
                          np.argsort(-k.astype(np.int64), kind="stable"))


def test_order_by_negative_ints_and_floats(rng):
    n = 1500
    a = rng.integers(-(1 << 20), 1 << 20, n).astype(np.int32)
    f = (rng.standard_normal(n) * 1e6).astype(np.float32)
    t = Table({"a": a, "f": f})
    out = order_by(t, ["a", "f"]).to_numpy()
    perm = np.asarray(jnp.lexsort((t.column("f"), t.column("a"))))
    assert np.array_equal(out["a"], a[perm])
    assert np.array_equal(out["f"], f[perm])


def test_order_by_float64_multiword(rng):
    x = rng.standard_normal(700) * 1e12
    t = Table({"x": x, "i": np.arange(700, dtype=np.int32)})
    out = order_by(t, "x").to_numpy()
    perm = np.argsort(x, kind="stable")
    assert out["x"].dtype == np.float64
    assert np.array_equal(out["x"], x[perm])
    assert np.array_equal(out["i"], perm)


def test_sort_rowids_multiword_matches_lexsort(rng):
    n = 1200
    words = jnp.asarray(
        rng.integers(0, 1 << 32, (n, 3), dtype=np.uint64).astype(np.uint32))
    sorted_words, rowids = sort_rowids(words, 96)
    w = np.asarray(words)
    perm = np.asarray(jnp.lexsort((words[:, 2], words[:, 1], words[:, 0])))
    assert np.array_equal(np.asarray(rowids), perm)
    assert np.array_equal(np.asarray(sorted_words), w[perm])


@pytest.mark.parametrize("dist", ["uniform", "zipf", "all_equal"])
def test_group_by_matches_segment_oracle(rng, dist):
    n = 4000
    if dist == "uniform":
        g = rng.integers(0, 50, n)
    elif dist == "zipf":
        g = np.clip(rng.zipf(1.3, n) - 1, 0, 63)
    else:
        g = np.zeros(n)
    g = g.astype(np.int32)
    v = rng.integers(-1000, 1000, n).astype(np.int32)
    t = Table({"g": g, "v": v})
    out = group_by(t, "g", {"total": ("v", "sum"), "cnt": (None, "count"),
                            "lo": ("v", "min"), "hi": ("v", "max")}).to_numpy()
    uniq = np.unique(g)
    assert np.array_equal(out["g"], uniq)
    # pure-XLA oracle: sort by key, segment-reduce
    order = jnp.argsort(t.column("g"))
    gs = np.asarray(t.column("g")[order])
    vs = t.column("v")[order]
    seg = np.searchsorted(uniq, gs)
    import jax
    k = len(uniq)
    assert np.array_equal(out["total"], np.asarray(
        jax.ops.segment_sum(vs, jnp.asarray(seg), num_segments=k)))
    assert np.array_equal(out["cnt"], np.asarray(
        jax.ops.segment_sum(jnp.ones_like(vs), jnp.asarray(seg),
                            num_segments=k)))
    assert np.array_equal(out["lo"], np.asarray(
        jax.ops.segment_min(vs, jnp.asarray(seg), num_segments=k)))
    assert np.array_equal(out["hi"], np.asarray(
        jax.ops.segment_max(vs, jnp.asarray(seg), num_segments=k)))


def test_group_by_composite_key_with_float64(rng):
    n = 2500
    a = rng.integers(0, 4, n).astype(np.int32)
    x = rng.standard_normal(n) * 1e6  # float64 key component (multi-word)
    v = rng.integers(0, 100, n).astype(np.int32)
    t = Table({"a": a, "x": x, "v": v})
    out = group_by(t, ["a", "x"], {"s": ("v", "sum")}).to_numpy()
    # oracle: python dict over exact key pairs
    want = {}
    for ai, xi, vi in zip(a, x, v):
        want[(int(ai), float(xi))] = want.get((int(ai), float(xi)), 0) + vi
    assert len(out["a"]) == len(want)
    for ai, xi, si in zip(out["a"], out["x"], out["s"]):
        assert want[(int(ai), float(xi))] == si


@pytest.mark.parametrize("dup", ["unique_right", "dup_both"])
def test_join_matches_oracle(rng, dup):
    nl, nr = 1500, 400
    if dup == "unique_right":
        rk = rng.permutation(1 << 10)[:nr].astype(np.int32)
    else:
        rk = rng.integers(0, 64, nr).astype(np.int32)  # duplicate-heavy
    lk = rng.integers(0, 1 << 10 if dup == "unique_right" else 64,
                      nl).astype(np.int32)
    left = Table({"k": lk, "lv": np.arange(nl, dtype=np.int32)})
    right = Table({"k": rk, "rv": np.arange(nr, dtype=np.int32)})
    out = sort_merge_join(left, right, "k").to_numpy()
    # oracle: every (l, r) key match, sorted by (key, l arrival, r arrival)
    want = sorted((int(k), lv, rv)
                  for k, lv in zip(lk, range(nl))
                  for k2, rv in zip(rk, range(nr)) if k == k2)
    assert len(out["k"]) == len(want)
    got = list(zip(out["k"].tolist(), out["lv"].tolist(),
                   out["rv"].tolist()))
    assert got == want


def test_join_composite_key_and_payload_gather(rng):
    n = 800
    a = rng.integers(0, 8, n).astype(np.int32)
    b = rng.integers(-4, 4, n).astype(np.int32)
    left = Table({"a": a, "b": b, "amt": rng.integers(0, 100, n)
                  .astype(np.int32)})
    m = 300
    a2 = rng.integers(0, 8, m).astype(np.int32)
    b2 = rng.integers(-4, 4, m).astype(np.int32)
    right = Table({"a": a2, "b": b2, "amt": rng.integers(0, 100, m)
                   .astype(np.int32)})
    out = sort_merge_join(left, right, ["a", "b"],
                          codecs={"a": IntCodec(4), "b": IntCodec(4)}
                          ).to_numpy()
    want = sum(1 for i in range(n) for j in range(m)
               if a[i] == a2[j] and b[i] == b2[j])
    assert len(out["a"]) == want
    # clashing non-key column gets suffixed on both sides
    assert "amt_l" in out and "amt_r" in out
    la = {(int(x), int(y)): [] for x, y in zip(a, b)}
    for x, y, amt in zip(a, b, np.asarray(left.column("amt"))):
        la[(int(x), int(y))].append(int(amt))
    for x, y, amt in zip(out["a"], out["b"], out["amt_l"]):
        assert int(amt) in la[(int(x), int(y))]


def _structured(cols: dict) -> np.ndarray:
    """Key columns as one numpy structured array (field-by-field — i.e.
    lexicographic — comparison: the multi-word join oracle)."""
    n = len(next(iter(cols.values())))
    out = np.zeros((n,), np.dtype([(k, v.dtype) for k, v in cols.items()]))
    for k, v in cols.items():
        out[k] = v
    return out


def _join_oracle_pairs(lk: dict, rk: dict):
    """Matching (left row, right row) pairs in the operator's output
    order — key-sorted, ties by (left arrival, right arrival) — computed
    entirely on structured arrays."""
    ls, rs = _structured(lk), _structured(rk)
    rperm = np.argsort(rs, kind="stable")
    rss = rs[rperm]
    lo = np.searchsorted(rss, ls, side="left")
    hi = np.searchsorted(rss, ls, side="right")
    return [(int(lpos), int(rperm[j]))
            for lpos in np.argsort(ls, kind="stable")
            for j in range(lo[lpos], hi[lpos])]


def _check_multiword_join(left_keys: dict, right_keys: dict, codecs=None):
    nl = len(next(iter(left_keys.values())))
    nr = len(next(iter(right_keys.values())))
    left = Table({**left_keys, "lv": np.arange(nl, dtype=np.int32)})
    right = Table({**right_keys, "rv": np.arange(nr, dtype=np.int32)})
    out = sort_merge_join(left, right, list(left_keys), codecs=codecs)
    want = _join_oracle_pairs(left_keys, right_keys)
    got = list(zip(np.asarray(out.column("lv")).tolist(),
                   np.asarray(out.column("rv")).tolist()))
    assert got == want


def test_join_multiword_float64(rng):
    """64-bit (two-word) float64 join keys, duplicate-heavy, including
    values that share the high code word and differ only in the low
    mantissa word (cross-word-boundary ties are real matches/misses)."""
    pool = np.array([1.0, 1.0 + 2.0 ** -40, 1.0 + 2.0 ** -20,
                     -3.5, -3.5 - 2.0 ** -41, 0.0, 7.25], np.float64)
    lk = pool[rng.integers(0, len(pool), 400)]
    rk = pool[rng.integers(0, len(pool), 150)]
    _check_multiword_join({"x": lk}, {"x": rk})


def test_join_multiword_composite_64(rng):
    """(int32, int32) composite: 64-bit code, word 0 = first column —
    rows equal in word 0 and differing across the boundary must tie-break
    on word 1 exactly as one wide integer key."""
    _check_multiword_join(
        {"a": rng.integers(-4, 4, 600).astype(np.int32),
         "b": rng.integers(-3, 3, 600).astype(np.int32)},
        {"a": rng.integers(-4, 4, 200).astype(np.int32),
         "b": rng.integers(-3, 3, 200).astype(np.int32)})


def test_join_multiword_three_words_uneven_tail(rng):
    """(int32, int32, int16) = 80-bit code: three words, the last only 16
    bits wide — ties that differ only inside the short tail word."""
    _check_multiword_join(
        {"a": rng.integers(-2, 2, 300).astype(np.int32),
         "b": rng.integers(-2, 2, 300).astype(np.int32),
         "c": rng.integers(-8, 8, 300).astype(np.int16)},
        {"a": rng.integers(-2, 2, 120).astype(np.int32),
         "b": rng.integers(-2, 2, 120).astype(np.int32),
         "c": rng.integers(-8, 8, 120).astype(np.int16)})


def test_words_searchsorted_matches_structured(rng):
    """The lexicographic merge probe ≡ numpy structured searchsorted on
    random word matrices (duplicates everywhere)."""
    from repro.query.operators import _words_searchsorted

    for W in (2, 3):
        m, n = 500, 300
        sw = np.sort(_structured(
            {f"w{j}": rng.integers(0, 4, m).astype(np.uint32)
             for j in range(W)}), kind="stable")
        sorted_words = np.stack([sw[f"w{j}"] for j in range(W)], axis=1)
        queries = np.stack(
            [rng.integers(0, 5, n).astype(np.uint32) for _ in range(W)],
            axis=1)
        qs = _structured(
            {f"w{j}": queries[:, j] for j in range(W)})
        for side in ("left", "right"):
            got = _words_searchsorted(sorted_words, queries, side)
            want = np.searchsorted(sw, qs, side=side)
            assert np.array_equal(got, want), (W, side)


def test_join_rejects_mismatched_column_widths(rng):
    """Same total bits on both sides but swapped per-column widths must be
    rejected, not silently return an empty join."""
    left = Table({"a": np.zeros(4, np.int8), "b": np.zeros(4, np.int16)})
    right = Table({"a": np.zeros(4, np.int16), "b": np.zeros(4, np.int8)})
    with pytest.raises(AssertionError, match="identically"):
        sort_merge_join(left, right, ["a", "b"])


def test_operator_outputs_compose(rng):
    """Key columns decode back to their inferred dtype, so an operator's
    output re-infers the same codec — group_by → join round trips."""
    n = 600
    u = rng.integers(0, 1 << 16, n).astype(np.uint16)
    t = Table({"u": u, "v": rng.integers(0, 50, n).astype(np.int32)})
    g = group_by(t, "u", {"s": ("v", "sum")})
    assert np.dtype(g.column("u").dtype) == np.uint16
    j = sort_merge_join(t, g, "u")  # same inferred codec on both sides
    assert j.num_rows == n
    i8 = rng.integers(-128, 128, n).astype(np.int8)
    t8 = Table({"k": i8, "v": np.arange(n, dtype=np.int32)})
    d = distinct(t8, "k")
    assert np.dtype(d.column("k").dtype) == np.int8
    assert sort_merge_join(t8, d, "k").num_rows == n


def test_distinct_first_occurrence(rng):
    n = 2000
    k = rng.integers(0, 9, n).astype(np.int32)
    t = Table({"k": k, "row": np.arange(n, dtype=np.int32)})
    out = distinct(t, "k").to_numpy()
    uniq = np.unique(k)
    assert np.array_equal(out["k"], uniq)
    firsts = np.asarray([np.flatnonzero(k == u)[0] for u in uniq])
    assert np.array_equal(out["row"], firsts)  # DISTINCT ON: first arrival


def test_top_k_matches_sorted_head(rng):
    n = 1777
    f = (rng.standard_normal(n) * 50).astype(np.float32)
    t = Table({"f": f, "row": np.arange(n, dtype=np.int32)})
    for k in (1, 10, n + 5):
        out = top_k(t, [("f", "desc")], k).to_numpy()
        want = np.asarray(-jnp.sort(-t.column("f")))[:k]
        assert np.array_equal(out["f"], want)


def test_operators_on_empty_table():
    t = Table({"k": np.zeros(0, np.int32), "v": np.zeros(0, np.int32)})
    assert order_by(t, "k").num_rows == 0
    assert distinct(t, "k").num_rows == 0
    g = group_by(t, "k", {"s": ("v", "sum"), "c": (None, "count")})
    assert g.num_rows == 0
    j = sort_merge_join(t, t, "k")
    assert j.num_rows == 0


def test_infer_codec_widths(rng):
    assert infer_codec(np.zeros(3, np.int8)).bits == 8
    assert infer_codec(np.zeros(3, np.int32)).bits == 32
    assert infer_codec(np.zeros(3, np.float64)).bits == 64
    assert infer_codec(jnp.zeros(3, jnp.float32)).bits == 32
    assert infer_codec(np.zeros(3, np.int32), bits=9).bits == 9
    with pytest.raises(AssertionError):
        infer_codec(np.zeros(3, np.complex64))


# --- top_k MSD-histogram pruning ---------------------------------------------


@pytest.mark.parametrize("dist", ["uniform", "all_equal", "skew_low",
                                  "boundary_ties"])
def test_top_k_pruned_equals_full_sort_head(rng, dist):
    """top_k prunes via the leading-digit histogram before sorting; the
    result must equal order_by().head(k) exactly — rows, payload, and tie
    order — on distributions that stress the cut bin."""
    n = 4000
    if dist == "uniform":
        k_col = rng.integers(-5000, 5000, n).astype(np.int32)
    elif dist == "all_equal":
        k_col = np.full(n, 42, np.int32)  # every row lands in the cut bin
    elif dist == "skew_low":
        k_col = np.minimum(rng.zipf(1.3, n), 1 << 20).astype(np.int32)
    else:  # exactly k-straddling ties at the boundary value
        k_col = np.where(rng.random(n) < 0.5, 7, 9999).astype(np.int32)
    t = Table({"k": k_col, "row": np.arange(n, dtype=np.int32),
               "v": rng.standard_normal(n).astype(np.float32)})
    for k in (1, 13, 500, n - 1, n, n + 10):
        got = top_k(t, "k", k).to_numpy()
        want = order_by(t, "k").head(k).to_numpy()
        for col in ("k", "row", "v"):
            assert np.array_equal(got[col], want[col]), (dist, k, col)


def test_top_k_pruned_multiword_and_desc(rng):
    """Pruning must hold on multi-word codes (the histogram reads the most
    significant word) and under desc direction (bit-inverted codes)."""
    n = 3000
    t = Table({"d": rng.standard_normal(n).astype(np.float64),
               "row": np.arange(n, dtype=np.int32)})
    for by in ("d", [("d", "desc")]):
        for k in (5, 250):
            got = top_k(t, by, k).to_numpy()
            want = order_by(t, by).head(k).to_numpy()
            assert np.array_equal(got["row"], want["row"]), (by, k)
            assert np.array_equal(got["d"], want["d"]), (by, k)


def test_top_k_zero_and_negative_k(rng):
    t = Table({"k": rng.integers(0, 9, 100).astype(np.int32)})
    assert top_k(t, "k", 0).num_rows == 0
    assert top_k(t, "k", -3).num_rows == 0


# --- jit-cached sort_rowids chain + tuned/pinned plans -----------------------


def test_rowid_chain_is_cached_across_calls(rng):
    """The fused encode→sort chain must trace once per (codec, widths,
    plans) config: repeated order_by calls on same-shaped float64 keys hit
    the lru-cached jitted chain instead of re-dispatching per word."""
    from repro.query.operators import _fused_chain

    n = 1500
    t = Table({"d": rng.standard_normal(n).astype(np.float64)})
    order_by(t, "d")
    before = _fused_chain.cache_info()
    order_by(t, "d")
    after = _fused_chain.cache_info()
    assert after.hits > before.hits, "second call must reuse the chain"
    assert after.misses == before.misses


def test_sort_rowids_accepts_pinned_plans(rng):
    """Explicit per-word plans (caller-pinned) must flow through the
    chain and sort identically to the defaults."""
    from repro.core import make_sort_plan

    n = 2000
    d = rng.standard_normal(n).astype(np.float64)
    codec = infer_codec(d)
    words = codec.encode(d)
    plans = tuple(make_sort_plan(n, w, max_bins_log2=8, engine="scatter")
                  for w in word_widths(codec.bits))
    sw, rid = sort_rowids(words, codec.bits, plans)
    sw0, rid0 = sort_rowids(words, codec.bits)
    assert np.array_equal(np.asarray(rid), np.asarray(rid0))
    assert np.array_equal(np.asarray(sw), np.asarray(sw0))
    with pytest.raises(AssertionError, match="plans"):
        sort_rowids(words, codec.bits, plans[:1])


def test_codec_word_plans_resolve_per_word(rng):
    """Codec.word_plans sizes one tuned plan per emitted word — the
    codec-driven widths (not a global 32-bit default) reach the planner."""
    spec = [ColumnSpec(IntCodec(32)), ColumnSpec(IntCodec(9))]
    codec = CompositeCodec(spec)  # 41 bits -> words of 32 + 9
    plans = codec.word_plans(4096)
    assert [p.p for p in plans] == [32, 9]
    assert all(p.n == 4096 for p in plans)


def test_operators_accept_plans_kwarg(rng):
    """Every operator must accept (and correctly apply) pinned plans."""
    from repro.core import make_sort_plan

    n = 1200
    t = Table({"k": rng.integers(0, 100, n).astype(np.int32),
               "v": rng.integers(0, 10, n).astype(np.int32)})
    plans = (make_sort_plan(n, 32, max_bins_log2=8, engine="scatter"),)
    want = order_by(t, "k").to_numpy()
    got = order_by(t, "k", plans=plans).to_numpy()
    assert np.array_equal(got["k"], want["k"])
    assert np.array_equal(got["v"], want["v"])
    assert group_by(t, "k", {"c": (None, "count")},
                    plans=plans).num_rows == distinct(t, "k").num_rows
    tk = top_k(t, "k", 17, plans=plans).to_numpy()
    assert np.array_equal(tk["k"], want["k"][:17])


# --- device-to-host accounting -----------------------------------------------


def _moved_and_traced(fn):
    """``fn()``'s ``query.d2h_bytes`` delta and its trace."""
    from repro import obs
    from repro.obs import metrics

    with obs.tracing() as session:
        before = metrics.snapshot()
        fn()
        moved = metrics.snapshot_delta(before).get("query.d2h_bytes", 0)
    return moved, session.trace


@pytest.mark.parametrize("values_on", ["host", "device"])
def test_group_by_counts_each_transfer_once(rng, values_on):
    n = 1000
    vals = rng.standard_normal(n)
    t = Table({"k": jnp.asarray(rng.integers(0, 50, n).astype(np.int32)),
               "v": vals if values_on == "host"
               else jnp.asarray(vals.astype(np.float32))})
    moved, tr = _moved_and_traced(lambda: group_by(
        t, "k", {"s": ("v", "sum"), "c": (None, "count")}))
    # sorted uint32 words (one word) + int32 row ids + the (1,) probe mask,
    # and a device value column once
    assert moved == 4 * n + 4 * n + 4 + (4 * n if values_on == "device"
                                         else 0)
    tr.assert_well_formed()
    assert tr.total("query.fetch", "bytes") == moved
    phases = tr.summary()["query.group_by"]["children"]
    assert {"query.prepare", "query.probe", "query.chain", "query.fetch",
            "query.segments", "query.decode", "query.gather",
            "query.reduce"} <= set(phases)
    assert phases["query.probe"]["children"]["query.fetch"]["count"] == 1
    assert phases["query.gather"]["count"] == 1
    assert phases["query.gather"]["children"] == {}


@pytest.mark.parametrize("op", ["order_by", "distinct", "top_k",
                                "sort_merge_join"])
def test_operators_count_their_fetches(rng, op):
    n = 512
    t = Table({"k": jnp.asarray(rng.integers(0, 1 << 20, n).astype(np.int32)),
               "v": jnp.arange(n, dtype=jnp.int32)})
    run = {"order_by": lambda: order_by(t, "k"),
           "distinct": lambda: distinct(t, "k"),
           "top_k": lambda: top_k(t, "k", 5),
           "sort_merge_join": lambda: sort_merge_join(t, t, "k")}[op]
    moved, tr = _moved_and_traced(run)
    fetches = tr.find("query.fetch")
    assert moved > 0 and tr.total("query.fetch", "bytes") == moved
    (scope,) = tr.find(f"query.{op}")
    assert all(scope["t0"] <= s["t0"] and s["t1"] <= scope["t1"]
               for s in fetches)
    assert not tr.find("query.gather") and not tr.find("query.reduce")


# --- a TPC-H Q3-shaped plan: join -> join -> group_by -> top_k ---------------


def _q3_tables(rng, dist):
    """customer-, orders- and lineitem-like tables: ``a`` joins the first
    two, ``b`` the second to the third; float64 ``x`` takes whole values
    so that sums are exact and revenues tie."""
    if dist == "duplicate_heavy":
        ka, kb = 8, 30
    else:  # sparse keys over the widths the codecs declare
        ka, kb = 1 << 21, 1 << 26
    n_a, n_b, n_c = 300, 500, 2000
    b_keys = rng.integers(0, kb, n_b).astype(np.int32)
    A = Table({"a": jnp.asarray(rng.integers(0, ka, n_a).astype(np.int32))})
    a_of_b = (np.asarray(A.column("a"))[rng.integers(0, n_a, n_b)]
              if dist != "duplicate_heavy"
              else rng.integers(0, ka, n_b).astype(np.int32))
    B = Table({"a": jnp.asarray(a_of_b),
               "b": jnp.asarray(b_keys),
               "d": jnp.asarray(rng.integers(0, 20, n_b).astype(np.int32)),
               "p": jnp.asarray(rng.integers(0, 2, n_b).astype(np.int32))})
    C = Table({"b": jnp.asarray(b_keys[rng.integers(0, n_b, n_c)]),
               "x": rng.integers(1, 4, n_c).astype(np.float64)})
    return A, B, C


def _q3_oracle(A, B, C, k):
    a = np.asarray(A.column("a"))
    ba, bb, bd, bp = (np.asarray(B.column(c)).astype(np.int64)
                      for c in ("a", "b", "d", "p"))
    cb, cx = np.asarray(C.column("b")).astype(np.int64), C.column("x")
    count_a = np.bincount(a, minlength=int(ba.max()) + 1)[ba]
    rows, inv = np.unique(cb, return_inverse=True)
    at = np.minimum(np.searchsorted(rows, bb), rows.size - 1)
    hit = rows[at] == bb
    sum_c = np.where(hit, np.bincount(inv, cx)[at], 0.0)
    cnt_c = np.where(hit, np.bincount(inv)[at], 0)
    keys, ginv = np.unique(np.stack([bb, bd, bp], 1), axis=0,
                           return_inverse=True)
    ginv = ginv.ravel()
    rev = np.bincount(ginv, count_a * sum_c, len(keys))
    n = np.bincount(ginv, count_a * cnt_c, len(keys)).astype(np.int64)
    keys, rev, n = keys[n > 0], rev[n > 0], n[n > 0]
    top = np.lexsort((keys[:, 2], keys[:, 0], keys[:, 1], -rev))[:k]
    return keys, rev, n, top


@pytest.mark.parametrize("codecs", ["declared", "inferred"])
@pytest.mark.parametrize("dist", ["duplicate_heavy", "sparse"])
def test_q3_plan_matches_numpy_oracle(rng, dist, codecs):
    """Two joins, a three-column GROUP BY and a top-k by (sum desc, date)
    match a numpy oracle exactly, row for row: every joined row counts
    once (duplicate keys on both sides of both joins), and revenue ties
    keep the GROUP BY's key order."""
    A, B, C = _q3_tables(rng, dist)
    cod = ({"a": UIntCodec(21), "b": UIntCodec(26), "d": UIntCodec(14),
            "p": UIntCodec(1)} if codecs == "declared" else None)
    j1 = sort_merge_join(A, B, "a", codecs=cod)
    j2 = sort_merge_join(j1.select(["b", "d", "p"]), C, "b", codecs=cod)
    g = group_by(j2, ["b", "d", "p"], {"rev": ("x", "sum"),
                                      "n": (None, "count")}, codecs=cod)
    top = top_k(g, [("rev", "desc"), "d"], 10, codecs=cod)
    keys, rev, n, order = _q3_oracle(A, B, C, 10)
    got = np.stack([np.asarray(g.column(c)).astype(np.int64)
                    for c in ("b", "d", "p")], 1)
    np.testing.assert_array_equal(got, keys)
    np.testing.assert_array_equal(np.asarray(g.column("rev")), rev)
    np.testing.assert_array_equal(np.asarray(g.column("n")), n)
    assert len(j2) == n.sum()
    got_top = np.stack([np.asarray(top.column(c)).astype(np.int64)
                        for c in ("b", "d", "p")], 1)
    np.testing.assert_array_equal(got_top, keys[order])
    np.testing.assert_array_equal(np.asarray(top.column("rev")), rev[order])
    assert len(np.unique(rev[order])) < len(order)  # the top holds ties


def _counted(fn):
    from repro.obs import metrics

    before = metrics.snapshot()
    out = fn()
    return out, metrics.snapshot_delta(before)


def test_join_counts_rows_sorted_and_out(rng):
    """``query.rows_sorted`` adds both sides' rows, ``query.sort_min_bytes``
    their int32 keys and row ids (8 B a row), ``query.join_rows_out`` the
    output's rows."""
    left = Table({"k": jnp.asarray(rng.integers(0, 40, 700).astype(np.int32))})
    right = Table({"k": jnp.asarray(rng.integers(0, 40, 300).astype(np.int32)),
                   "v": rng.standard_normal(300)})
    out, d = _counted(lambda: sort_merge_join(left, right, "k"))
    assert d["query.rows_sorted"] == 1000
    assert d["query.sort_min_bytes"] == 1000 * (4 + 4)
    assert d["query.join_rows_out"] == len(out) > 1000


@pytest.mark.parametrize("op", ["group_by", "top_k", "empty"])
def test_sort_counters_follow_the_chains_that_ran(rng, op):
    """A float64 key is 8 prepared bytes a row and a uint16 one 2; a
    pruned top-k sorts only its candidates; an empty table runs no
    chain and counts nothing."""
    n = 4000
    t = Table({"f": rng.standard_normal(n),
               "u": jnp.asarray(rng.integers(0, 1 << 16, n).astype(np.uint16))})
    if op == "group_by":
        _, d = _counted(lambda: group_by(t, ["f", "u"], {"c": (None, "count")}))
        assert d["query.rows_sorted"] == n
        assert d["query.sort_min_bytes"] == n * (8 + 2 + 4)
    elif op == "top_k":
        _, d = _counted(lambda: top_k(t, "u", 5))
        rows = d["query.rows_sorted"]
        assert 5 <= rows < n
        assert d["query.sort_min_bytes"] == rows * (2 + 4)
    else:
        _, d = _counted(lambda: group_by(t.head(0), "u",
                                         {"c": (None, "count")}))
        assert "query.rows_sorted" not in d
        assert "query.sort_min_bytes" not in d
