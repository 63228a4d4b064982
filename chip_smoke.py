"""Smoke run of the sort, query and stream paths on a TPU.

    python chip_smoke.py [--seed S]     # one chip
    python chip_smoke.py --chips 4      # the cross-device paths only

One process drives the chip through the entry points users call, checks
every output bit-exactly against a plain reference (``jax.lax.sort``,
``jnp.argsort`` or numpy), and prints as its last line

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

It exits non-zero, with no such line, when JAX finds no TPU or any check
fails.  Times printed on the way are smoke timings of one run each, not
benchmark numbers.  JAX's persistent compile cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in ``.jax_cache``
next to this file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.fractal_sort_paper import (  # noqa: E402
    PAPER_NATIVE_PLAN,
    PAPER_P16,
    PAPER_P32,
)
from repro.core import distributed_fractal_sort  # noqa: E402
from repro.core.fractal_sort import fractal_sort  # noqa: E402
from repro.core.sort_plan import make_sort_plan  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.fractal_rank import fractal_rank_counts  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.query import IntCodec, Table, group_by, order_by, sort_merge_join  # noqa: E402
from repro.stream import (  # noqa: E402
    ArraySource,
    DeviceShardStore,
    MemoryBudget,
    RunStore,
    external_sort,
)

NOTE = "(smoke timing, one run; not a benchmark number)"


@dataclasses.dataclass(frozen=True)
class Sizes:
    """log2 of every phase's size; ``FULL`` is what the chip runs."""

    sort: int = 27             # 2**27 uint32 keys = 512 MB, the paper's low end
    native_max: int = 27       # the paper-native plan steps down from here
    query_rows: int = 24
    query_ids: int = 20
    stream: int = 26
    kernel_sort: int = 24
    moe_tokens: int = 13       # x top-8 = 65536 routed ids
    distributed: int = 27
    distributed_stream: int = 24


FULL = Sizes()


def check(ok, what: str) -> None:
    if not bool(ok):
        raise SystemExit(f"FAIL: {what}")
    print(f"  ok: {what}", flush=True)


def keys_on_device(seed: int, tag: int, n: int, p: int):
    """``n`` uniform ``p``-bit keys made on the device from the seed."""
    k = jax.random.fold_in(jax.random.key(seed), tag)
    u = jax.random.bits(k, (n,), jnp.uint32)
    if p == 32:
        return u
    return (u >> (32 - p)).astype(jnp.int32)


def compile_timed(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def run_timed(compiled, *args):
    """First run, then a warm run timed to ``block_until_ready``."""
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, time.perf_counter() - t0


def same(a, b) -> bool:
    return a.shape == b.shape and a.dtype == b.dtype and \
        bool(jnp.array_equal(a, b))


# -- phases ------------------------------------------------------------------


def phase_sort(seed: int, sz: Sizes) -> None:
    """In-HBM fractal_sort at the paper's size, p=32 and p=16."""
    n = 1 << sz.sort
    for wl in (PAPER_P32, PAPER_P16):
        keys = keys_on_device(seed, wl.p, n, wl.p)
        plan = make_sort_plan(n, wl.p)
        print(f"sort {wl.name} n=2^{sz.sort} plan: {plan.describe()}")
        c, t_cold = compile_timed(lambda k: fractal_sort(k, wl.p, plan=plan),
                                  keys)
        _, t_warm = compile_timed(lambda k: fractal_sort(k, wl.p, plan=plan),
                                  keys)
        print(f"  compile_s cold={t_cold:.3f} again={t_warm:.3f} {NOTE}")
        out, wall = run_timed(c, keys)
        print(f"  warm_wall_s={wall:.6f} {NOTE}")
        ref = jax.block_until_ready(jax.jit(jax.lax.sort)(keys))
        check(same(out, ref), f"fractal_sort {wl.name} == jax.lax.sort")
        del keys, out, ref


def phase_native(seed: int, sz: Sizes) -> None:
    """The paper-native 16-bit plan at the largest power of two whose
    compiled program fits the device's memory."""
    limit = jax.devices()[0].memory_stats()
    limit = limit.get("bytes_limit") if limit else None
    w = PAPER_NATIVE_PLAN.max_bins_log2
    for lg in range(sz.native_max, 9, -1):
        n = 1 << lg
        plan = make_sort_plan(n, 32, max_bins_log2=w)
        spec = jax.ShapeDtypeStruct((n,), jnp.uint32)
        try:
            c, t = compile_timed(lambda k: fractal_sort(k, 32, plan=plan),
                                 spec)
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"paper-native n=2^{lg}: does not fit "
                  f"({str(e).splitlines()[0][:160]})")
            continue
        m = c.memory_analysis()
        need = (m.argument_size_in_bytes + m.output_size_in_bytes
                + m.temp_size_in_bytes)
        if limit is not None and need > limit:
            print(f"paper-native n=2^{lg}: needs {need} B > {limit} B")
            continue
        print(f"paper-native n=2^{lg} plan: {plan.describe()}")
        print(f"  temp_bytes={m.temp_size_in_bytes} total_bytes={need} "
              f"compile_s={t:.3f} {NOTE}")
        keys = keys_on_device(seed, 100 + lg, n, 32)
        out, wall = run_timed(c, keys)
        print(f"  warm_wall_s={wall:.6f} {NOTE}")
        ref = jax.jit(jax.lax.sort)(keys)
        check(same(out, ref), f"paper-native n=2^{lg} == jax.lax.sort")
        return
    raise SystemExit("FAIL: the paper-native plan fits at no size")


def phase_query(seed: int, sz: Sizes) -> None:
    """order_by, group_by and sort_merge_join on a seeded orders table."""
    n, m = 1 << sz.query_rows, 1 << sz.query_ids
    rng = np.random.default_rng(seed)
    cid = rng.integers(0, m, n).astype(np.int32)
    amount = np.round(rng.gamma(2.0, 30.0, n), 2).astype(np.float32)
    oid = np.arange(n, dtype=np.int32)
    orders = Table({"oid": oid, "cid": cid, "amount": amount})
    segment = rng.integers(0, 5, m).astype(np.int32)
    customers = Table({"cid": np.arange(m, dtype=np.int32),
                       "segment": segment})
    codecs = {"cid": IntCodec(bits=sz.query_ids + 1)}
    print(f"query orders=2^{sz.query_rows} rows, customers=2^{sz.query_ids}")

    t0 = time.perf_counter()
    ranked = order_by(orders, [("amount", "desc"), ("cid", "asc")],
                      codecs=codecs)
    got = np.asarray(ranked.column("oid"))
    print(f"  order_by wall_s={time.perf_counter() - t0:.3f} "
          f"(first call, compile included) {NOTE}")
    want = np.lexsort((cid, -amount))
    check(np.array_equal(got, oid[want]), "order_by == numpy lexsort")

    by_cid = np.argsort(cid, kind="stable")
    starts = np.flatnonzero(np.r_[True, np.diff(cid[by_cid]) != 0])
    t0 = time.perf_counter()
    grouped = group_by(orders, "cid", {"total": ("amount", "sum"),
                                       "orders": (None, "count")},
                       codecs=codecs).to_numpy()
    print(f"  group_by wall_s={time.perf_counter() - t0:.3f} "
          f"(first call, compile included) {NOTE}")
    check(np.array_equal(grouped["cid"], cid[by_cid][starts]),
          "group_by keys == numpy")
    check(np.array_equal(grouped["orders"], np.diff(starts, append=n)),
          "group_by count == numpy")
    check(np.array_equal(grouped["total"],
                         np.add.reduceat(amount[by_cid], starts)),
          "group_by sum == numpy")

    t0 = time.perf_counter()
    joined = sort_merge_join(orders, customers, "cid",
                             codecs=codecs).to_numpy()
    print(f"  sort_merge_join wall_s={time.perf_counter() - t0:.3f} "
          f"(first call, compile included) {NOTE}")
    check(np.array_equal(joined["oid"], oid[by_cid])
          and np.array_equal(joined["cid"], cid[by_cid])
          and np.array_equal(joined["segment"], segment[cid[by_cid]]),
          "sort_merge_join == numpy")


def _external(keys: np.ndarray, p: int, store) -> tuple:
    budget = MemoryBudget(limit_bytes=keys.nbytes // 8)
    source = ArraySource(keys, budget.rows(keys.itemsize))
    t0 = time.perf_counter()
    out = np.concatenate(list(external_sort(source, p, budget, store=store)))
    return out, budget, time.perf_counter() - t0


def phase_stream(seed: int, sz: Sizes) -> None:
    """external_sort under a budget of one eighth of the data, spilling
    to the default disk RunStore."""
    keys = keys_on_device(seed, 200, 1 << sz.stream, 32)
    ref = np.asarray(jax.jit(jax.lax.sort)(keys))
    keys = np.asarray(keys)
    out, budget, wall = _external(keys, 32, None)
    print(f"stream n=2^{sz.stream} budget={budget.limit_bytes} B "
          f"peak={budget.peak_bytes} B wall_s={wall:.3f} "
          f"(compile included) {NOTE}")
    check(np.array_equal(out, ref), "external_sort == jax.lax.sort")
    check(budget.peak_bytes <= budget.limit_bytes,
          "external_sort peak_bytes <= limit_bytes")


def phase_kernels(seed: int, sz: Sizes, interpret: bool = False) -> None:
    """The Pallas path, compiled: the kernel sort and MoE dispatch."""
    n = 1 << sz.kernel_sort
    keys = keys_on_device(seed, 300, n, 32)
    c, t = compile_timed(
        lambda k: ops.fractal_sort_kernel(k, 32, interpret=interpret), keys)
    out, wall = run_timed(c, keys)
    print(f"kernel fractal_sort_kernel n=2^{sz.kernel_sort} "
          f"interpret={interpret} compile_s={t:.3f} warm_wall_s={wall:.6f} "
          f"{NOTE}")
    check(same(out, jax.jit(jax.lax.sort)(keys)),
          "fractal_sort_kernel == jax.lax.sort")
    try:
        fractal_rank_counts(keys[:1024].astype(jnp.int32) & 15, 16,
                            interpret=False, engine="scatter")
        raised = False
    except NotImplementedError:
        raised = True
    check(raised, "the scatter rank engine, asked for compiled, raises")

    moe = get_config("qwen3-moe-30b-a3b").moe
    tokens = 1 << sz.moe_tokens
    logits = jax.random.normal(jax.random.key(seed + 1),
                               (tokens, moe.num_experts))
    ids = jax.lax.top_k(logits, moe.top_k)[1].reshape(-1).astype(jnp.int32)
    c, t = compile_timed(
        lambda i: ops.moe_dispatch(i, moe.num_experts, interpret=interpret),
        ids)
    (perm, rank, counts), wall = run_timed(c, ids)
    print(f"kernel moe_dispatch E={moe.num_experts} top-{moe.top_k} "
          f"ids={ids.shape[0]} compile_s={t:.3f} warm_wall_s={wall:.6f} "
          f"{NOTE}")
    want = jnp.argsort(ids, stable=True).astype(jnp.int32)
    check(same(perm, want), "moe_dispatch perm == stable jnp.argsort")
    check(same(rank, jnp.argsort(want).astype(jnp.int32)),
          "moe_dispatch rank == inverse permutation")
    check(same(counts, jnp.bincount(ids, length=moe.num_experts)
               .astype(jnp.int32)), "moe_dispatch counts == bincount")


def phase_distributed(seed: int, sz: Sizes, chips: int) -> None:
    """distributed_fractal_sort over a ``chips``-device mesh, and the
    external sort with fragments placed on that mesh."""
    mesh = make_mesh((chips,), ("data",))
    n = 1 << sz.distributed
    keys = jax.device_put(keys_on_device(seed, 400, n, 32),
                          NamedSharding(mesh, P("data")))
    ref = jax.jit(jax.lax.sort)(keys)
    t0 = time.perf_counter()
    out, overflow = distributed_fractal_sort(keys, mesh, "data", 32)
    jax.block_until_ready(out)
    print(f"distributed_fractal_sort n=2^{sz.distributed} over {chips} "
          f"devices wall_s={time.perf_counter() - t0:.3f} "
          f"(compile included) {NOTE}")
    check(not bool(overflow), "distributed_fractal_sort no bucket overflow")
    check(same(out, ref), "distributed_fractal_sort == jax.lax.sort")
    del keys, out, ref

    keys = np.asarray(keys_on_device(seed, 500, 1 << sz.distributed_stream, 32))
    on_disk, _, t_disk = _external(keys, 32, RunStore())
    store = DeviceShardStore()
    on_mesh, budget, t_mesh = _external(keys, 32, store)
    devices = sorted({d for _, d in store.device_log})
    print(f"stream n=2^{sz.distributed_stream} DeviceShardStore "
          f"fragments={len(store.device_log)} devices={devices} "
          f"wall_s={t_mesh:.3f}, RunStore wall_s={t_disk:.3f} "
          f"(compile included) {NOTE}")
    check(np.array_equal(on_mesh, on_disk),
          "external_sort DeviceShardStore == RunStore")
    check(np.array_equal(on_disk, np.sort(keys)),
          "external_sort RunStore == np.sort")
    check(devices == list(range(chips)),
          f"fragments landed on all {chips} devices")
    check(budget.peak_bytes <= budget.limit_bytes,
          "DeviceShardStore peak_bytes <= limit_bytes")


# -- entry point -------------------------------------------------------------


def use_compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself),
    else a fixed directory next to this file."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-device phases on 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind} device_count={len(devices)}")
    if dev.platform != "tpu":
        raise SystemExit(f"FAIL: no TPU (jax platform {dev.platform!r})")
    if len(devices) < args.chips:
        raise SystemExit(f"FAIL: {args.chips} chips asked, "
                         f"{len(devices)} present")
    print(f"compile cache: {use_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_distributed(args.seed, FULL, 4)
    else:
        phase_sort(args.seed, FULL)
        phase_native(args.seed, FULL)
        phase_query(args.seed, FULL)
        phase_stream(args.seed, FULL)
        phase_kernels(args.seed, FULL)
    print(f"all phases passed in {time.perf_counter() - t0:.1f} s {NOTE}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
