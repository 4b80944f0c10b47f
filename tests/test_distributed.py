"""Multi-device tests: run in a subprocess with 8 forced host devices
(XLA locks the device count at first init, so the main pytest process —
which sees 1 device — cannot host these)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_py(code: str, devices: int = 8, timeout: int = 500):
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_PLATFORMS="cpu",
    )
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


def test_distributed_pagerank_matches_single_device():
    """shard_map block-parallel PR over 8 devices == host numpy oracle."""
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core import rmat, build_block_store, build_schedule
        from repro.core.distributed import DistributedEngine
        from repro.algorithms import pagerank_algorithm, pagerank

        g = rmat(9, 8, seed=3)
        store = build_block_store(g, 4)
        sched = build_schedule(pagerank_algorithm(), store, num_devices=8,
                               mode="sparse_only")
        inv_deg = jnp.asarray(1.0 / np.maximum(np.diff(store.indptr), 1))
        n = store.n

        def edge_update(src, dst, valid, state):
            contrib = state["rank"] * inv_deg
            vals = jnp.where(valid, contrib[src], 0.0)
            acc = jnp.zeros(n, jnp.float32).at[dst].add(vals)
            return dict(rank=state["rank"], acc=acc)

        eng = DistributedEngine(store, sched, edge_update,
                                combine=dict(rank="max", acc="add"))
        state = dict(rank=jnp.full((n,), 1.0 / n), acc=jnp.zeros(n))
        dangling = jnp.asarray(np.diff(store.indptr) == 0)
        for _ in range(20):
            state = eng.step(state)
            dm = jnp.sum(jnp.where(dangling, state["rank"], 0.0))
            rank = 0.15 / n + 0.85 * (state["acc"] + dm / n)
            state = dict(rank=rank, acc=jnp.zeros(n))
        got = np.asarray(state["rank"])

        store2 = build_block_store(g, 4)
        want = pagerank(store2, mode="sparse_only")
        err = float(np.abs(got - want).max())
        assert err < 1e-5, err
        print("DIST_OK", err)
    """)
    assert "DIST_OK" in r.stdout, r.stdout + r.stderr


def test_lpt_devices_reduce_wallclock_imbalance():
    r = _run_py("""
        import numpy as np, jax
        from repro.core import rmat, build_block_store, build_schedule
        from repro.core.distributed import make_device_edge_partition
        from repro.algorithms import pagerank_algorithm

        g = rmat(10, 8, seed=1)
        store = build_block_store(g, 8)
        sched = build_schedule(pagerank_algorithm(), store, num_devices=8,
                               mode="sparse_only")
        part = make_device_edge_partition(store, sched)
        loads = part["valid"].sum(1)
        ratio = loads.max() / max(loads.mean(), 1)
        assert ratio < 1.35, ratio     # LPT keeps devices balanced
        # every edge appears exactly once across devices
        assert int(part["valid"].sum()) == store.m
        print("LPT_OK", float(ratio))
    """)
    assert "LPT_OK" in r.stdout, r.stdout + r.stderr


def test_mini_dryrun_8dev_mesh():
    """lower+compile a smoke arch on a (4,2) mesh with real shardings —
    the dry-run machinery end-to-end at test scale."""
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models.sharding import (
            set_mesh_ctx, param_specs, named_sharding_tree, batch_spec)
        from repro.models.steps import (
            make_train_step, abstract_params, abstract_opt_state)
        from repro.configs.base import ShapeSpec

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ctx = set_mesh_ctx(mesh)
        cfg = get_smoke("qwen2.5-32b")
        p_shapes = abstract_params(cfg)
        o_shapes = abstract_opt_state(cfg)
        p_sh = named_sharding_tree(ctx, param_specs(ctx, p_shapes))
        o_sh = named_sharding_tree(ctx, param_specs(ctx, o_shapes))
        specs = dict(
            tokens=jax.ShapeDtypeStruct((8, 64), jnp.int32),
            labels=jax.ShapeDtypeStruct((8, 64), jnp.int32),
        )
        b_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, batch_spec(ctx, s.shape)), specs)
        rep = NamedSharding(mesh, P())
        with mesh:
            step = make_train_step(cfg)
            jf = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh, rep),
                         out_shardings=(p_sh, o_sh, rep))
            lowered = jf.lower(p_shapes, o_shapes, specs,
                               jax.ShapeDtypeStruct((), np.int32))
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            assert mem.temp_size_in_bytes > 0
            hlo = compiled.as_text()
            from repro.roofline import collective_bytes_from_hlo
            coll = collective_bytes_from_hlo(hlo)
            assert coll["total"] > 0, "expected collectives in SPMD program"
            print("MINI_DRYRUN_OK", int(coll["total"]))
    """)
    assert "MINI_DRYRUN_OK" in r.stdout, r.stdout + r.stderr


def test_mini_dryrun_executes_on_8dev():
    """Not just compile — actually run one sharded train step on 8 devices."""
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models import lm
        from repro.models.sharding import (
            set_mesh_ctx, param_specs, named_sharding_tree)
        from repro.models.steps import make_train_step
        from repro.optim import adamw_init

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ctx = set_mesh_ctx(mesh)
        cfg = get_smoke("qwen2.5-32b")
        with mesh:
            params = lm.init_params(cfg, jax.random.key(0))
            p_sh = named_sharding_tree(ctx, param_specs(ctx, params))
            params = jax.device_put(params, p_sh)
            opt = adamw_init(params)
            batch = dict(
                tokens=jnp.zeros((8, 64), jnp.int32),
                labels=jnp.zeros((8, 64), jnp.int32),
            )
            step = jax.jit(make_train_step(cfg))
            p2, o2, m = step(params, opt, batch, jnp.int32(0))
            loss = float(m["loss"])
            assert np.isfinite(loss)
            print("EXEC_OK", loss)
    """)
    assert "EXEC_OK" in r.stdout, r.stdout + r.stderr


def test_elastic_restore_onto_8dev_mesh():
    """Checkpoint written on 1 device restores + trains on an (4,2) mesh."""
    r = _run_py("""
        import os, tempfile
        import numpy as np, jax, jax.numpy as jnp
        from dataclasses import replace
        from repro.configs import get_smoke
        from repro.models import lm
        from repro.models.sharding import (
            set_mesh_ctx, param_specs, named_sharding_tree)
        from repro.models.steps import make_train_step
        from repro.optim import adamw_init
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from jax.sharding import AxisType

        cfg = replace(get_smoke("qwen2.5-32b"), dtype="float32")
        params = lm.init_params(cfg, jax.random.key(0))
        state = dict(params=params, opt=adamw_init(params))
        d = tempfile.mkdtemp()
        save_checkpoint(d, 0, state)  # written host-side (1-device logical)

        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        ctx = set_mesh_ctx(mesh)
        template = jax.eval_shape(lambda: state)
        sh = dict(
            params=named_sharding_tree(ctx, param_specs(ctx, template["params"])),
            opt=named_sharding_tree(ctx, param_specs(ctx, template["opt"])),
        )
        restored, step = restore_checkpoint(d, template, shardings=sh)
        with mesh:
            batch = dict(tokens=jnp.zeros((8, 32), jnp.int32),
                         labels=jnp.zeros((8, 32), jnp.int32))
            stepf = jax.jit(make_train_step(cfg))
            p2, o2, m = stepf(restored["params"], restored["opt"], batch,
                              jnp.int32(step))
            assert np.isfinite(float(m["loss"]))
        # round-trip: values identical to the saved ones
        a = jax.device_get(restored["params"]["embed"])
        b = jax.device_get(params["embed"])
        assert np.allclose(a, b)
        print("ELASTIC_OK", float(m["loss"]))
    """)
    assert "ELASTIC_OK" in r.stdout, r.stdout + r.stderr


def test_grad_compression_dp_loop_8dev():
    """int8-compressed DP psum with error feedback converges on 8 shards."""
    r = _run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax import shard_map
        from jax.sharding import AxisType, Mesh, PartitionSpec as P
        from repro.optim import compressed_psum, error_feedback_init

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        w_true = jnp.asarray(np.random.default_rng(0).standard_normal(16))

        def local_grad(w, x):
            # per-shard quadratic: grad of mean((x@w - x@w_true)^2)
            err = x @ (w - w_true)
            return 2 * x.T @ err / x.shape[0]

        @jax.jit
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P("data", None, None, None), P()),
                 out_specs=(P(), P()), check_vma=False)
        def step(w, x, r):
            g = local_grad(w, x[0, 0])
            g, r = compressed_psum(dict(w=g), dict(w=r), "data")
            return g["w"], r["w"]

        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((8, 1, 64, 16)).astype(np.float32))
        w = jnp.zeros(16)
        resid = jnp.zeros(16)
        for i in range(200):
            g, resid = step(w, x, resid)
            w = w - 0.05 * g
        err = float(jnp.abs(w - w_true).max())
        assert err < 2e-2, err
        print("COMPRESS_OK", err)
    """)
    assert "COMPRESS_OK" in r.stdout, r.stdout + r.stderr
