"""Multi-device tests — each runs in a subprocess with 8 forced host
devices so the main test process keeps seeing exactly 1 device."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh
        mesh8 = make_mesh((8,), ("data",))
        mesh24 = make_mesh((2, 4), ("data", "model"))
    """) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600,
                       # JAX_PLATFORMS=cpu: the image ships libtpu; without
                       # the pin jax probes for a TPU and hangs the child.
                       env={"PYTHONPATH": "src",
                            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
                            "HOME": os.environ.get("HOME", "/root"),
                            "JAX_PLATFORMS": "cpu"},
                       cwd=REPO_ROOT)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_distributed_sort_distributions():
    out = _run("""
        from repro.core import distributed_fractal_sort
        rng = np.random.default_rng(1)
        cases = {
            "uniform": rng.integers(0, 1 << 16, 1 << 13).astype(np.int32),
            "zipf": np.clip(rng.zipf(1.3, 1 << 13), 0, 65535).astype(np.int32),
            "equal": np.full(1 << 13, 9, np.int32),
            "sorted": np.sort(rng.integers(0, 65536, 1 << 13)).astype(np.int32),
        }
        for name, keys in cases.items():
            ks = jax.device_put(jnp.asarray(keys), NamedSharding(mesh8, P("data")))
            got, ov = distributed_fractal_sort(ks, mesh8, "data", 16)
            assert not bool(ov), name
            assert bool((got == jnp.sort(ks)).all()), name
        # p=32 two-pass
        k32 = rng.integers(0, 1 << 32, 1 << 12, dtype=np.uint64).astype(np.uint32)
        ks = jax.device_put(jnp.asarray(k32), NamedSharding(mesh8, P("data")))
        got, ov = distributed_fractal_sort(ks, mesh8, "data", 32)
        assert not bool(ov)
        assert np.array_equal(np.asarray(got), np.sort(k32))
        print("DIST_SORT_OK")
    """)
    assert "DIST_SORT_OK" in out


def test_distributed_wide_field_scatter_rank():
    """The paper's ICI scheme (one all_to_all per 16-bit field,
    max_bins_log2=16): the per-device local rank of the 2**16-bin field
    now routes through the scatter engine — exact placement and stable
    argsort must survive the engine swap under shard_map."""
    out = _run("""
        from repro.core import (distributed_fractal_argsort,
                                distributed_fractal_sort)
        rng = np.random.default_rng(3)
        k32 = rng.integers(0, 1 << 32, 1 << 12, dtype=np.uint64).astype(np.uint32)
        ks = jax.device_put(jnp.asarray(k32), NamedSharding(mesh8, P("data")))
        got, ov = distributed_fractal_sort(ks, mesh8, "data", 32,
                                           max_bins_log2=16)
        assert not bool(ov)
        assert np.array_equal(np.asarray(got), np.sort(k32))
        dup = rng.choice([7, 9, 1 << 20], 1 << 12).astype(np.uint32)
        ds = jax.device_put(jnp.asarray(dup, jnp.uint32),
                            NamedSharding(mesh8, P("data")))
        perm, ov = distributed_fractal_argsort(ds, mesh8, "data", 32,
                                               max_bins_log2=16)
        assert not bool(ov)
        assert np.array_equal(np.asarray(perm), np.argsort(dup, kind="stable"))
        print("DIST_WIDE_OK")
    """)
    assert "DIST_WIDE_OK" in out


def test_compressed_psum_error_feedback():
    out = _run("""
        import functools
        from repro.optim import compressed_psum
        rng = np.random.default_rng(0)
        g = rng.normal(size=(8, 256)).astype(np.float32)
        gs = jax.device_put(jnp.asarray(g), NamedSharding(mesh8, P("data")))

        def body(x, err):
            return compressed_psum(x, "data", err)

        f = jax.jit(shard_map(body, mesh=mesh8, in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data")), check_vma=False))
        err = jnp.zeros_like(gs)
        mean, err = f(gs, err)
        want = g.mean(axis=0, keepdims=True).repeat(8, 0)
        # int8 quantization: ~1% relative error on the mean
        rel = np.abs(np.asarray(mean) - want).max() / np.abs(want).max()
        assert rel < 0.02, rel
        # error feedback: feeding the residual back reduces accumulated bias
        total_err_1 = np.abs(np.asarray(err)).mean()
        mean2, err2 = f(gs, err)
        better = np.abs(np.asarray(mean2) - want).max() / np.abs(want).max()
        assert better < 0.02
        print("PSUM_OK")
    """)
    assert "PSUM_OK" in out


def test_moe_shard_map_matches_single_device():
    """The shard_map expert-parallel MoE must equal the no-mesh path."""
    out = _run("""
        import dataclasses
        from repro.configs import get_config, smoke_config
        from repro.models import transformer as T, act_sharding
        from repro import sharding as SH
        cfg = smoke_config(get_config("qwen3-moe-30b-a3b"))
        # no-drop capacity: per-shard capacity binds differently than the
        # single-device global capacity (drop patterns would differ)
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=float(cfg.moe.num_experts)))
        key = jax.random.PRNGKey(0)
        params = T.init_params(key, cfg)
        tokens = jax.random.randint(key, (4, 16), 0, cfg.vocab)

        act_sharding.set_batch_axes(None)
        ref_logits, ref_aux = T.forward(params, cfg, tokens)

        act_sharding.set_batch_axes(("data",), mesh24)
        p_sh = SH.param_shardings(params, mesh24, cfg)
        params_s = jax.tree.map(jax.device_put, params, p_sh)
        tokens_s = jax.device_put(tokens, NamedSharding(mesh24, P("data")))
        with mesh24:
            logits, aux = jax.jit(lambda p, t: T.forward(p, cfg, t))(params_s, tokens_s)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                                   rtol=2e-4, atol=2e-4)
        print("MOE_SHARD_OK")
    """)
    assert "MOE_SHARD_OK" in out


def test_sharded_train_step_runs():
    """End-to-end sharded train step on a 2x4 mesh (FSDP x TP)."""
    out = _run("""
        from repro.configs import get_config, smoke_config
        from repro.models import transformer as T
        from repro import optim as O, train_lib as TL, sharding as SH
        from repro.data import DataConfig, SyntheticLM
        cfg = smoke_config(get_config("llama3.2-1b"))
        key = jax.random.PRNGKey(0)
        params = T.init_params(key, cfg)
        oc = O.OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=4)
        opt = O.init_opt_state(params, oc)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4))
        step = TL.shard_train_step(TL.make_train_step(cfg, oc), mesh24,
                                   params, opt, data.batch(0), cfg)
        p_sh = SH.param_shardings(params, mesh24, cfg)
        params = jax.tree.map(jax.device_put, params, p_sh)
        losses = []
        for i in range(3):
            params, opt, m = step(params, opt, data.batch(i))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        print("TRAIN_SHARD_OK", losses)
    """)
    assert "TRAIN_SHARD_OK" in out


def test_split_kv_decode_matches_dense():
    """Sequence-parallel split-KV decode == single-device attention."""
    out = _run("""
        from repro.configs import get_config, smoke_config
        from repro.models import layers as L
        cfg = smoke_config(get_config("llama3.2-1b"))
        key = jax.random.PRNGKey(0)
        p = L.attn_init(key, cfg, jnp.float32)
        B, S = 2, 64
        x = jax.random.normal(key, (B, 1, cfg.d_model))
        ck = jax.random.normal(jax.random.fold_in(key, 1),
                               (B, S, cfg.n_kv_heads, cfg.resolved_head_dim))
        cv = jax.random.normal(jax.random.fold_in(key, 2), ck.shape)
        pos = jnp.asarray(S - 1)
        ref, _, _ = L.attn_decode(p, cfg, x, ck, cv, pos, update_cache=False)

        import functools
        body = functools.partial(L.attn_decode, p, cfg, update_cache=False,
                                 kv_seq_axis="data")
        f = shard_map(lambda x_, k_, v_, pos_: body(x_, k_, v_, pos_)[0],
                      mesh=mesh8,
                      in_specs=(P(), P(None, "data"), P(None, "data"), P()),
                      out_specs=P(), check_vma=False)
        got = f(x, ck, cv, pos)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        print("SPLIT_KV_OK")
    """)
    assert "SPLIT_KV_OK" in out


def test_compressed_ddp_train_step():
    """DDP training with int8-wire gradient reduction tracks uncompressed
    training closely (error feedback bounds the drift)."""
    out = _run("""
        from repro.configs import get_config, smoke_config
        from repro.models import transformer as T, act_sharding
        from repro import optim as O, train_lib as TL
        from repro.data import DataConfig, SyntheticLM
        act_sharding.set_batch_axes(None)
        cfg = smoke_config(get_config("llama3.2-1b"))
        key = jax.random.PRNGKey(0)
        params = T.init_params(key, cfg)
        oc = O.OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=8)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=16,
                                      global_batch=8))
        # uncompressed reference
        ref_p = params
        ref_o = O.init_opt_state(params, oc)
        ref_step = jax.jit(TL.make_train_step(cfg, oc))
        # compressed DDP over 8 shards
        cp = params
        co = O.init_opt_state(params, oc)
        err = TL.init_error_feedback(params, mesh8, "data")
        cstep = TL.make_compressed_ddp_step(cfg, oc, mesh8, "data")
        ref_losses, c_losses = [], []
        for i in range(4):
            b = data.batch(i)
            ref_p, ref_o, m = ref_step(ref_p, ref_o, b)
            ref_losses.append(float(m["loss"]))
            cp, co, err, cm = cstep(cp, co, err, b)
            c_losses.append(float(cm["loss"]))
        assert all(np.isfinite(c_losses))
        # same data, loss trajectories match to quantization tolerance
        for a, b_ in zip(ref_losses, c_losses):
            assert abs(a - b_) / max(abs(a), 1e-6) < 0.05, (ref_losses, c_losses)
        print("DDP_COMPRESSED_OK")
    """)
    assert "DDP_COMPRESSED_OK" in out


def test_elastic_restore_across_meshes():
    """Checkpoint written under a 2x4 mesh restores onto an 8x1 mesh
    (elastic restart on a different topology)."""
    out = _run("""
        import tempfile, os
        from repro.configs import get_config, smoke_config
        from repro.models import transformer as T
        from repro import checkpoint as CK, sharding as SH
        cfg = smoke_config(get_config("llama3.2-1b"))
        key = jax.random.PRNGKey(0)
        params = T.init_params(key, cfg)
        p_sh24 = SH.param_shardings(params, mesh24, cfg)
        params24 = jax.tree.map(jax.device_put, params, p_sh24)
        d = tempfile.mkdtemp()
        CK.save(d, 5, params24)
        mesh81 = make_mesh((8, 1), ("data", "model"))
        p_sh81 = SH.param_shardings(params, mesh81, cfg)
        back = CK.restore(d, 5, params, shardings=p_sh81)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
