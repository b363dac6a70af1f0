"""Multi-device tests (8 fake CPU devices via subprocess — the main pytest
process must keep seeing 1 device for the smoke tests).

Covers: pjit'd train step on a (4,2) data×model mesh with real loss descent,
sharding-spec consistency, pipeline parallelism vs sequential reference,
compressed cross-pod psum with error feedback, and elastic re-mesh restore.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 540) -> str:
    prelude = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        import sys
        sys.path.insert(0, {os.path.join(REPO, 'src')!r})
        from jax.sharding import AxisType
        AUTO1 = (AxisType.Auto,)
        AUTO2 = (AxisType.Auto,) * 2
    """)
    r = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


def test_pjit_train_step_descends_on_mesh():
    out = run_sub("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, reduced
        from repro.models import lm
        from repro.distributed import sharding as shd
        from repro.distributed.context import activation_mesh
        from repro.train.optimizer import OptConfig
        from repro.train.trainer import make_train_step, TrainConfig

        mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
        cfg = dataclasses.replace(reduced(get_config("qwen2-7b")),
                                  d_model=64, vocab=256,
                                  vocab_pad_multiple=64)
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        p_specs = shd.param_pspecs(cfg, params, mesh)
        p_shard = shd.to_shardings(mesh, p_specs)
        params = jax.device_put(params, p_shard)

        step_fn, _ = make_train_step(cfg, OptConfig(lr=5e-2), TrainConfig(
            steps=60, warmup=2, donate=False), mesh=mesh)
        from repro.train.optimizer import init_opt_state
        from repro.data import TokenStore, synthetic_corpus, token_batches
        opt_state = init_opt_state(OptConfig(lr=5e-2), params)
        store = TokenStore(synthetic_corpus(100_000, cfg.vocab), cfg.vocab)
        data = token_batches(store, cfg, batch=8, seq=16)
        losses = []
        with activation_mesh(mesh):
            for i in range(50):
                params, opt_state, m = step_fn(params, opt_state, next(data),
                                               jnp.asarray(i))
                losses.append(float(m["loss"]))
        assert losses[-1] < losses[0] - 0.5, losses
        # params stayed sharded
        leaf = params["blocks"][0]["mlp"]["wu"]
        assert not leaf.sharding.is_fully_replicated
        print("DESCENT", losses[0], "->", losses[-1])
    """)
    assert "DESCENT" in out


def test_param_specs_divisible_everywhere():
    """Every spec'd axis must divide the dim for all 10 archs on the
    production mesh (the invariant behind 'compiles on 16x16')."""
    out = run_sub("""
        import jax
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config, ARCH_IDS
        from repro.distributed import sharding as shd
        from repro.models import lm

        mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=AUTO2)
        sizes = dict(mesh.shape)
        for arch in ARCH_IDS:
            cfg = get_config(arch)
            tree = lm.param_specs(cfg)
            specs = shd.param_pspecs(cfg, tree, mesh)
            flat_specs = jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P))
            flat_shapes = jax.tree_util.tree_leaves(tree)
            assert len(flat_specs) == len(flat_shapes)
            for spec, leaf in zip(flat_specs, flat_shapes):
                for i, entry in enumerate(spec):
                    axes = entry if isinstance(entry, tuple) else \
                        (entry,) if entry else ()
                    n = int(np.prod([sizes[a] for a in axes])) if axes else 1
                    assert leaf.shape[i] % n == 0, (arch, spec, leaf.shape)
        print("DIVISIBLE-OK")
    """, devices=8)
    assert "DIVISIBLE-OK" in out


def test_pipeline_parallel_matches_sequential():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline_par import (pipelined_forward,
                                                    bubble_fraction)
        mesh = jax.make_mesh((4,), ("stage",), axis_types=AUTO1)
        rng = np.random.default_rng(0)
        S, M, MB, D = 4, 6, 8, 16
        w = jnp.asarray(rng.standard_normal((S, D, D)) * 0.3, jnp.float32)

        def stage_fn(p, x):
            return jnp.tanh(x @ p)

        x = jnp.asarray(rng.standard_normal((M, MB, D)), jnp.float32)
        piped = pipelined_forward(stage_fn, mesh)
        got = piped(w, x)
        want = x
        for i in range(S):
            want = jnp.tanh(want @ w[i])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
        print("PIPELINE-OK")
    """, devices=4)
    assert "PIPELINE-OK" in out


def test_compressed_psum_error_feedback():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import (psum_compressed,
                                                   compression_ratio)
        from repro.distributed.compat import shard_map
        mesh = jax.make_mesh((4,), ("pod",), axis_types=AUTO1)
        rng = np.random.default_rng(0)
        g_all = jnp.asarray(rng.standard_normal((4, 64, 32)), jnp.float32)

        def worker(g_local, err):
            red, new_err = psum_compressed({"w": g_local[0]}, "pod",
                                           {"w": err[0]})
            return red["w"], new_err["w"][None]

        sharded = shard_map(worker, mesh=mesh,
                            in_specs=(P("pod"), P("pod")),
                            out_specs=(P(), P("pod")))
        err = jnp.zeros((4, 64, 32), jnp.float32)
        exact = np.asarray(g_all.sum(0))
        red, err = sharded(g_all, err)
        got = np.asarray(red)
        rel = np.abs(got - exact).max() / np.abs(exact).max()
        assert rel < 0.05, rel
        # error feedback: residuals are nonzero and bounded by a code step
        assert float(jnp.abs(err).max()) > 0
        assert compression_ratio({"w": g_all}) > 3.5
        print("COMPRESS-OK", rel)
    """, devices=4)
    assert "COMPRESS-OK" in out


def test_elastic_remesh_checkpoint_restore():
    """Save on a (4,2) mesh, restore onto (2,2) with 4 'surviving' devices."""
    out = run_sub("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.train import checkpoint as ck
        from repro.train.fault import plan_elastic_mesh

        mesh1 = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO2)
        tree = {"w": jax.device_put(
            jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
            NamedSharding(mesh1, P("data", "model")))}
        d = tempfile.mkdtemp()
        ck.save(d, 5, tree)

        plan = plan_elastic_mesh(4, model_parallel=2)
        assert plan.shape == (2, 2)
        mesh2 = jax.make_mesh(plan.shape, plan.axes,
                              devices=np.array(jax.devices()[:4]),
                              axis_types=AUTO2)
        sh2 = {"w": NamedSharding(mesh2, P("data", "model"))}
        step, restored, _ = ck.restore_latest(d, tree, shardings=sh2)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64).reshape(8, 8))
        assert restored["w"].sharding.mesh.shape == {"data": 2, "model": 2}
        print("ELASTIC-OK")
    """, devices=8)
    assert "ELASTIC-OK" in out
