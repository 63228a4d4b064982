"""Optimizer, checkpointing, runtime fault-tolerance, data pipeline."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as CK
from repro import optim as O
from repro import runtime as RT
from repro.data import DataConfig, Prefetcher, SyntheticLM, length_bucketed_order


# --- optimizer -------------------------------------------------------------


def test_adamw_optimizes_quadratic():
    oc = O.OptimizerConfig(lr=0.1, warmup_steps=0, total_steps=100,
                           weight_decay=0.0, grad_clip=100.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = O.init_opt_state(params, oc)
    for _ in range(60):
        grads = {"w": 2 * params["w"]}  # d/dw w^2
        params, state, m = O.adamw_update(params, grads, state, oc)
    assert float(jnp.abs(params["w"]).max()) < 0.5


@pytest.mark.parametrize("mdt", ["float32", "bfloat16"])
def test_adamw_moment_dtype(mdt):
    oc = O.OptimizerConfig(moment_dtype=mdt)
    params = {"w": jnp.ones((4,))}
    state = O.init_opt_state(params, oc)
    assert state["mu"]["w"].dtype == jnp.dtype(mdt)
    params, state, _ = O.adamw_update(params, {"w": jnp.ones((4,))}, state, oc)
    assert state["mu"]["w"].dtype == jnp.dtype(mdt)


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}  # norm 5
    clipped, norm = O.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(5.0)
    n2 = float(jnp.linalg.norm(clipped["a"]))
    assert n2 == pytest.approx(1.0, rel=1e-5)


def test_cosine_schedule_shape():
    oc = O.OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                           min_lr_ratio=0.1)
    lrs = [float(O.cosine_lr(jnp.asarray(s), oc)) for s in range(101)]
    assert lrs[0] == 0.0
    assert lrs[10] == pytest.approx(1.0)
    assert lrs[100] == pytest.approx(0.1, rel=1e-3)
    assert all(a >= b - 1e-9 for a, b in zip(lrs[10:], lrs[11:]))  # decays


# --- checkpointing ----------------------------------------------------------


def _tree():
    return {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.asarray([1, 2, 3])}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    CK.save(str(tmp_path), 7, t)
    assert CK.latest_step(str(tmp_path)) == 7
    back = CK.restore(str(tmp_path), 7, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_k_and_atomicity(tmp_path):
    t = _tree()
    for s in range(5):
        CK.save(str(tmp_path), s, t, keep=2)
    steps = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert steps == ["step_000000003", "step_000000004"]
    assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


def test_checkpoint_async(tmp_path):
    ck = CK.AsyncCheckpointer(str(tmp_path), keep=3)
    ck.save_async(1, _tree())
    ck.wait()
    assert CK.latest_step(str(tmp_path)) == 1


def test_checkpoint_elastic_reshard(tmp_path):
    """Restore with explicit shardings (elastic restart onto a new mesh)."""
    t = _tree()
    CK.save(str(tmp_path), 3, t)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), t)
    back = CK.restore(str(tmp_path), 3, t, shardings=sh)
    assert back["a"].sharding == NamedSharding(mesh, P())


# --- runtime fault tolerance -------------------------------------------------


def test_straggler_monitor_flags_outliers():
    m = RT.StragglerMonitor(threshold=2.0)
    for _ in range(5):
        assert not m.observe(1.0)
    assert m.observe(5.0)  # 5x the EWMA
    assert m.flagged == 1
    assert not m.observe(1.0)  # recovery


def test_run_with_restarts_recovers():
    calls = []
    fails = {"n": 0}

    def step(s):
        if s == 3 and fails["n"] < 2:
            fails["n"] += 1
            raise RuntimeError("boom")
        calls.append(s)

    def restore():
        return 2  # last checkpoint

    end = RT.run_with_restarts(step, 0, 6, restore, max_restarts=3)
    assert end == 6
    assert calls.count(2) == 3  # replayed from checkpoint twice
    assert calls[-1] == 5


def test_run_with_restarts_crash_loop_raises():
    def step(s):
        raise RuntimeError("always")

    with pytest.raises(RuntimeError):
        RT.run_with_restarts(step, 0, 3, lambda: 0, max_restarts=2)


def test_step_journal(tmp_path):
    j = RT.StepJournal(str(tmp_path / "j.jsonl"))
    assert j.last_step() is None
    j.append(1, loss=2.0)
    j.append(2, loss=1.5)
    assert j.last_step() == 2
    recs = [json.loads(l) for l in open(tmp_path / "j.jsonl")]
    assert recs[1]["loss"] == 1.5


# --- data pipeline -----------------------------------------------------------


def test_data_determinism_and_restart_safety():
    cfg = DataConfig(vocab=100, seq_len=8, global_batch=4, seed=3)
    a, b = SyntheticLM(cfg), SyntheticLM(cfg)
    for s in (0, 5, 5, 17):  # restarts replay identical batches
        np.testing.assert_array_equal(np.asarray(a.batch(s)["tokens"]),
                                      np.asarray(b.batch(s)["tokens"]))
    c = SyntheticLM(DataConfig(vocab=100, seq_len=8, global_batch=4, seed=4))
    assert not np.array_equal(np.asarray(a.batch(0)["tokens"]),
                              np.asarray(c.batch(0)["tokens"]))


def test_length_bucketed_order(rng):
    lengths = jnp.asarray(rng.integers(1, 2000, 512), jnp.int32)
    order = length_bucketed_order(lengths)
    sorted_lens = np.asarray(lengths)[np.asarray(order)]
    assert np.all(np.diff(sorted_lens) >= 0)


def test_prefetcher():
    cfg = DataConfig(vocab=50, seq_len=4, global_batch=2)
    src = SyntheticLM(cfg)
    pf = Prefetcher(src, put_fn=lambda b: b, depth=2)
    for s in range(4):
        np.testing.assert_array_equal(np.asarray(pf.get(s)["tokens"]),
                                      np.asarray(src.batch(s)["tokens"]))
