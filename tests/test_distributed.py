"""Multi-device behaviour (8 placeholder host devices via subprocess):
sharding rules, compressed cross-pod psum, expert-parallel MoE equivalence,
and one real dry-run cell.  Subprocesses are required because
xla_force_host_platform_device_count must be set before jax initializes.
"""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_pspecs_rules_and_divisibility():
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs.base import get_config
        from repro.models.model import bundle_for
        from repro.models.sharding import param_pspecs, set_rules
        from repro.launch.mesh import rules_for

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("qwen3-1.7b")
        set_rules(rules_for(cfg, model_axis=4))
        bundle = bundle_for(cfg)
        shapes = jax.eval_shape(lambda k: bundle.init(cfg, k),
                                jax.ShapeDtypeStruct((2,), jnp.uint32))
        with jax.set_mesh(mesh):
            specs = param_pspecs(shapes)
        wq = specs["blocks"]["attn"]["wq"]
        assert wq == P(None, None, "model"), wq      # stacked leading dim
        emb = specs["embed"]["table"]
        assert emb == P("model", None), emb          # vocab over model
        norm = specs["final_norm"]["w"]
        assert norm == P(None), norm
        # n_heads*hd = 16*128 = 2048 divisible by 4 ok; norm replicated ok
        print("PSPECS_OK")
    """)
    assert "PSPECS_OK" in out


def test_compressed_psum_matches_plain_psum():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum_pod

        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4, 2), ("pod", "data"))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 64)).astype(np.float32))

        def plain(x):
            return jax.lax.psum(x, "pod")

        def compressed(x):
            return compressed_psum_pod(x, "pod")

        sm = lambda f: jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=P("pod", None), out_specs=P("pod", None),
            check_vma=False))
        a = sm(plain)(x)
        b = sm(compressed)(x)
        err = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
        assert err < 0.05, err      # int8 quantization error bound
        print("COMPRESS_OK", err)
    """)
    assert "COMPRESS_OK" in out


def test_moe_expert_parallel_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import smoke_of
        from repro.models import moe as MoE
        from repro.models.sharding import set_rules
        from repro.launch.mesh import rules_for

        cfg = smoke_of("qwen3-moe-30b-a3b")   # 8 experts
        cfg = dataclasses.replace(cfg, dtype="float32", capacity_factor=8.0)
        key = jax.random.PRNGKey(0)
        p = MoE.init_moe(cfg, key, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))

        # single-shard reference (no mesh)
        set_rules({})
        y_ref, aux_ref = MoE.moe_block(cfg, p, x)

        # expert-parallel over a 4-way model axis
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        set_rules(rules_for(cfg, model_axis=4, force_tp=True))
        with jax.set_mesh(mesh):
            y_ep, aux_ep = jax.jit(
                lambda p, x: MoE.moe_block(cfg, p, x))(p, x)
        err = float(jnp.max(jnp.abs(y_ref - y_ep)))
        assert err < 1e-4, err
        aerr = abs(float(aux_ref) - float(aux_ep))
        assert aerr < 1e-5, aerr   # load-balance aux agrees across EP
        print("MOE_EP_OK", err, aerr)
    """)
    assert "MOE_EP_OK" in out


def test_grad_shardings_lower_and_compile():
    """A miniature version of the dry-run on 8 devices (fast)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import smoke_of, ShapeConfig
        from repro.models.model import bundle_for, input_specs
        from repro.models.sharding import param_pspecs, set_rules
        from repro.launch.mesh import rules_for
        from repro.optim import AdamW, constant
        from repro.train.step import make_train_step, train_state_shape

        cfg = smoke_of("qwen3-1.7b")
        shape = ShapeConfig("t", "train", 64, 8)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        set_rules(rules_for(cfg, model_axis=4))
        opt = AdamW(lr=constant(1e-4))
        with jax.set_mesh(mesh):
            st = train_state_shape(cfg, opt)
            sspec = param_pspecs(st)
            ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t)
            bspec = {"tokens": NamedSharding(mesh, P("data", None)),
                     "labels": NamedSharding(mesh, P("data", None))}
            step = make_train_step(cfg, opt, remat="dots")
            jf = jax.jit(step, in_shardings=(ns(sspec), bspec),
                         out_shardings=(ns(sspec), None),
                         donate_argnums=(0,))
            specs = input_specs(cfg, shape)
            compiled = jf.lower(st, specs).compile()
            assert compiled.cost_analysis() is not None
        print("MINI_DRYRUN_OK")
    """)
    assert "MINI_DRYRUN_OK" in out


def test_multipod_compressed_train_step_lowers():
    """Cross-pod int8 gradient compression inside the jitted train step."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import smoke_of, ShapeConfig
        from repro.models.sharding import param_pspecs, set_rules
        from repro.launch.mesh import rules_for
        from repro.optim import AdamW, constant
        from repro.train.step import make_train_step, train_state_shape

        cfg = smoke_of("qwen2-0.5b")
        shape = ShapeConfig("t", "train", 32, 8)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        set_rules(rules_for(cfg, model_axis=2))
        opt = AdamW(lr=constant(1e-4))
        with jax.set_mesh(mesh):
            st = train_state_shape(cfg, opt)
            sspec = param_pspecs(st)
            ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t)
            bspec = {"tokens": NamedSharding(mesh, P(("pod", "data"), None)),
                     "labels": NamedSharding(mesh, P(("pod", "data"), None))}
            step = make_train_step(cfg, opt, compress_pods=True, mesh=mesh)
            from repro.models.model import input_specs
            compiled = jax.jit(step, in_shardings=(ns(sspec), bspec),
                               out_shardings=(ns(sspec), None)
                               ).lower(st, input_specs(cfg, shape)).compile()
            hlo = compiled.as_text()
            assert "all-to-all" in hlo or "all-gather" in hlo
            assert "s8[" in hlo, "int8 wire format missing from HLO"
        print("COMPRESSED_STEP_OK")
    """)
    assert "COMPRESSED_STEP_OK" in out
