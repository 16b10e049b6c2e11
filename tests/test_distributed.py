"""Multi-device semantics, run in a subprocess with 8 forced host devices
(the main test process must keep seeing 1 device — see conftest)."""

import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str) -> str:
    code = textwrap.dedent(body)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, timeout=600,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_distributed_ring_join_exact():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.blocked import BlockedJoinConfig
        from repro.core.distributed import (
            DistributedJoinConfig, init_sharded_window, make_distributed_join_step)
        from repro.data.synth import dense_embedding_stream, planted_duplicates
        theta, lam, d = 0.8, 0.05, 64
        vecs, ts = dense_embedding_stream(256, d, seed=3, rate=2.0)
        truth = planted_duplicates(vecs, ts, theta, lam)
        mesh = jax.make_mesh((8,), ("data",))
        cfg = DistributedJoinConfig(base=BlockedJoinConfig(
            theta=theta, lam=lam, capacity=128, d=d,
            block_q=32, block_w=32, chunk_d=32))
        step = make_distributed_join_step(cfg, mesh)
        state = init_sharded_window(cfg, mesh)
        got, uid0 = set(), 0
        for i in range(0, 256, 64):
            q = jnp.asarray(vecs[i:i+64]); tq = jnp.asarray(ts[i:i+64], jnp.float32)
            uq = jnp.arange(uid0, uid0+64, dtype=jnp.int32)
            w_uids = np.asarray(state.uids)
            state, (s_win, s_self) = step(state, q, tq, uq)
            for a, b in zip(*np.nonzero(np.asarray(s_win))):
                got.add((min(uid0+a, w_uids[b]), max(uid0+a, w_uids[b])))
            for a, b in zip(*np.nonzero(np.asarray(s_self))):
                got.add((min(uid0+a, uid0+b), max(uid0+a, uid0+b)))
            uid0 += 64
        assert got == truth, (len(got), len(truth))
        print("ring join exact:", len(got))
    """)


def test_sharded_train_step_matches_single_device():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.distributed.sharding import DEFAULT_RULES, param_shardings, use_rules
        from repro.launch.mesh import make_mesh_for
        from repro.models.lm import lm_specs
        from repro.optim.adamw import AdamWConfig, opt_state_specs
        from repro.train.step import TrainConfig, build_train_step, init_train_state

        cfg = ARCHS["qwen3-0.6b"].reduced(n_layers=2, vocab_size=512)
        tc = TrainConfig(optimizer=AdamWConfig(peak_lr=1e-2, warmup_steps=1,
                                               total_steps=10),
                         remat=True, microbatches=1, z_loss=0.0,
                         compute_dtype="float32")
        rng = np.random.default_rng(0)
        t = jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 16)), jnp.int32)
        batch = {"tokens": t, "labels": t}

        # single device
        p1, o1 = init_train_state(jax.random.key(0), cfg, tc)
        step1 = jax.jit(build_train_step(cfg, tc))
        p1, o1, m1 = step1(p1, o1, batch)

        # 4×2 mesh (data × model)
        mesh = make_mesh_for((4, 2), ("data", "model"))
        p2, o2 = init_train_state(jax.random.key(0), cfg, tc)
        with use_rules(mesh, DEFAULT_RULES):
            specs = lm_specs(cfg)
            p2 = jax.device_put(p2, param_shardings(specs, p2, mesh, DEFAULT_RULES))
            o2 = jax.device_put(o2, param_shardings(
                opt_state_specs(specs, tc.optimizer), o2, mesh, DEFAULT_RULES))
        base = build_train_step(cfg, tc)
        def stepper(p, o, b):
            with use_rules(mesh, DEFAULT_RULES):
                return base(p, o, b)
        step2 = jax.jit(stepper)
        p2, o2, m2 = step2(p2, o2, batch)

        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (
            float(m1["loss"]), float(m2["loss"]))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-3, atol=3e-5)
        print("sharded step matches:", float(m1["loss"]), float(m2["loss"]))
    """)


def test_compressed_psum_error_feedback():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.launch.mesh import make_mesh_for
        from repro.train.grad_sync import compressed_psum, init_ef_state

        mesh = make_mesh_for((8,), ("pod",))
        rng = np.random.default_rng(0)
        # per-pod gradients (8, n) — psum over 'pod' should give the mean
        g_all = rng.standard_normal((8, 4, 512)).astype(np.float32)
        want = g_all.mean(0)

        def body(g, e):
            out, new_e = compressed_psum({"w": g[0]}, {"w": e[0]}, "pod")
            return out["w"][None], new_e["w"][None]

        f = jax.jit(shard_map(body, mesh=mesh,
                              in_specs=(P("pod"), P("pod")),
                              out_specs=(P("pod"), P("pod"))))
        e = jnp.zeros_like(jnp.asarray(g_all))
        out, e = f(jnp.asarray(g_all), e)
        got = np.asarray(out)[0]
        # single-step int8 error within quantization tolerance
        assert np.abs(got - want).max() < 0.02 * np.abs(g_all).max()

        # error feedback: averaging the SAME gradient over many steps
        # converges to the exact mean (residual re-injection)
        acc = np.zeros_like(want)
        e = jnp.zeros_like(jnp.asarray(g_all))
        steps = 20
        for _ in range(steps):
            out, e = f(jnp.asarray(g_all), e)
            acc += np.asarray(out)[0]
        acc /= steps
        assert np.abs(acc - want).max() < 2e-3, np.abs(acc - want).max()
        print("EF compression ok")
    """)


def test_checkpoint_reshard_across_meshes():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import tempfile
        from repro.ft.checkpoint import restore_checkpoint, save_checkpoint
        from repro.launch.mesh import make_mesh_for

        x = jnp.asarray(np.arange(64, dtype=np.float32).reshape(8, 8))
        mesh_a = make_mesh_for((8,), ("data",))
        xa = jax.device_put(x, NamedSharding(mesh_a, P("data")))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 1, {"x": xa})

        mesh_b = make_mesh_for((4, 2), ("data", "model"))
        sh_b = {"x": NamedSharding(mesh_b, P("data", "model"))}
        like = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        out, _, _ = restore_checkpoint(d + "/step_00000001", like, shardings=sh_b)
        np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(x))
        assert out["x"].sharding == sh_b["x"]
        print("reshard ok")
    """)


def test_long_context_decode_shards_kv_seq():
    """SP-decode: a reduced zamba2 decode with kv_seq sharded over model —
    the long_500k regime at test scale."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.configs import ARCHS
        from repro.distributed.sharding import use_rules
        from repro.launch.cells import LONG_RULES
        from repro.launch.mesh import make_mesh_for
        from repro.models.lm import (init_lm, init_lm_caches, lm_decode_step,
                                     lm_forward)
        cfg = ARCHS["zamba2-2.7b"].reduced()
        mesh = make_mesh_for((2, 4), ("data", "model"))
        params = init_lm(jax.random.key(0), cfg)
        B, S, M = 1, 16, 32
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
        logits_full, _, _ = lm_forward(params, cfg, tokens=toks,
                                       compute_dtype=jnp.float32)
        caches = init_lm_caches(cfg, B, M, dtype=jnp.float32)
        def pre(p, c, t):
            with use_rules(mesh, LONG_RULES):
                _, _, c2 = lm_forward(p, cfg, tokens=t, caches=c,
                                      cache_len=jnp.int32(0),
                                      compute_dtype=jnp.float32)
                return c2
        caches = jax.jit(pre)(params, caches, toks[:, :S-1])
        def dec(p, c, t):
            with use_rules(mesh, LONG_RULES):
                return lm_decode_step(p, cfg, tokens=t, caches=c,
                                      cache_len=jnp.int32(S-1),
                                      compute_dtype=jnp.float32)[0]
        out = jax.jit(dec)(params, caches, toks[:, S-1:])
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(logits_full[:, -1]), atol=2e-3)
        print("SP decode ok")
    """)
