"""Multi-device tests for the ICI-ring MSR encode and int8 gradient sync.

These need >1 device, so they run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps the host's single device, per DESIGN.md §8).
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_subprocess(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax
        import jax.numpy as jnp
        import numpy as np
        assert len(jax.devices()) == 8, jax.devices()
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=480)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def _spec_literal(k, p):
    """Search coefficients in THIS process (memoized) and inline the result,
    so the subprocess skips the condition-(6) search it isn't testing."""
    from repro.core.circulant import CodeSpec
    spec = CodeSpec.make(k, p)
    return f"CodeSpec(k={spec.k}, p={spec.p}, c={spec.c!r})"


def test_ring_encode_matches_dense_oracle():
    run_subprocess(f"""
        from repro.core.circulant import CodeSpec
        from repro.core.ring import ring_encode, ring_encode_reference
        from repro.launch.mesh import make_storage_mesh
        spec = {_spec_literal(4, 257)}                   # n = 8 nodes
        mesh = make_storage_mesh(8)
        rng = np.random.default_rng(0)
        # full-range symbols: int32 wire
        data = rng.integers(0, 257, size=(8, 4096), dtype=np.int64).astype(np.int32)
        with mesh:
            got = np.asarray(ring_encode(jnp.asarray(data), spec, mesh))
        want = np.asarray(ring_encode_reference(jnp.asarray(data), spec))
        np.testing.assert_array_equal(got, want)
        # systematic byte blocks: uint8 wire (4x less traffic) must agree
        dbytes = rng.integers(0, 256, size=(8, 4096), dtype=np.int64).astype(np.int32)
        with mesh:
            got8 = np.asarray(ring_encode(jnp.asarray(dbytes), spec, mesh,
                                          byte_wire=True))
        want8 = np.asarray(ring_encode_reference(jnp.asarray(dbytes), spec))
        np.testing.assert_array_equal(got8, want8)
        print("ring encode OK")
    """)


def test_ring_encode_various_sizes():
    from repro.core.circulant import CodeSpec
    cases = []
    for k, p, s in [(4, 257, 128), (4, 257, 1000), (4, 5, 64)]:
        try:
            CodeSpec.make(k, p)
        except ValueError:
            continue
        cases.append(f"({_spec_literal(k, p)}, {s})")
    run_subprocess("""
        from repro.core.circulant import CodeSpec
        from repro.core.ring import ring_encode, ring_encode_reference
        from repro.launch.mesh import make_storage_mesh
        for spec, s in [%s]:
            k, p = spec.k, spec.p
            mesh = make_storage_mesh(2 * k)
            rng = np.random.default_rng(k + s)
            data = rng.integers(0, p, size=(2 * k, s), dtype=np.int64).astype(np.int32)
            with mesh:
                got = np.asarray(ring_encode(jnp.asarray(data), spec, mesh))
            want = np.asarray(ring_encode_reference(jnp.asarray(data), spec))
            np.testing.assert_array_equal(got, want, err_msg=f"k={k} p={p} s={s}")
        print("sizes OK")
    """ % ", ".join(cases))


def test_int8_ring_mean_close_to_true_mean():
    run_subprocess("""
        from repro.optim.compression import int8_ring_mean
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(1)
        x = rng.normal(size=(8, 4096)).astype(np.float32)
        got = np.asarray(int8_ring_mean(jnp.asarray(x), mesh, "data"))
        want = x.mean(0)
        for row in got:
            err = np.abs(row - want).max()
            scale = np.abs(x).max() / 127
            assert err < 10 * scale, (err, scale)   # a few re-quantized hops
        print("int8 ring mean OK")
    """)


def test_sharded_train_step_runs_on_host_mesh():
    """End-to-end: jit train_step with the sharding policy on an 8-device
    host mesh (data=4, model=2) — the same policy the dry-run uses."""
    run_subprocess("""
        from jax.sharding import PartitionSpec as P
        import jax
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        from repro.configs import get_config
        from repro.models import Model
        from repro.optim import adamw
        from repro.launch.steps import make_train_step, input_specs
        from repro.sharding import policy, ctx as shctx
        from repro.configs.base import ShapeConfig

        cfg = get_config("qwen3-4b").reduced(
            n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
            d_ff=64, vocab_size=256, loss_chunk=16)
        model = Model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        state = {"params": params, "opt": adamw.init(params, opt_cfg)}
        pspecs = policy.param_specs(jax.eval_shape(lambda: params), mesh)
        state_sh = {"params": pspecs,
                    "opt": adamw.OptState(mu=pspecs, nu=pspecs, step=P())}
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 256, (8, 32)), jnp.int32)}
        bspecs = policy.batch_spec(jax.eval_shape(lambda: batch), mesh, global_batch=8)
        rules = policy.activation_rules(cfg, mesh, "train")
        with mesh, shctx.rules(mesh, rules):
            fn = jax.jit(make_train_step(model, opt_cfg, 2),
                         in_shardings=(policy.named(state_sh, mesh),
                                       policy.named(bspecs, mesh)),
                         donate_argnums=(0,))
            state2, metrics = fn(state, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss) and loss > 0, loss
        # compare against single-device reference
        params_ref = Model(cfg).init(jax.random.PRNGKey(0))
        state_ref = {"params": params_ref, "opt": adamw.init(params_ref, opt_cfg)}
        fn_ref = jax.jit(make_train_step(model, opt_cfg, 2), donate_argnums=(0,))
        _, m_ref = fn_ref(state_ref, batch)
        assert abs(loss - float(m_ref["loss"])) < 0.05, (loss, float(m_ref["loss"]))
        print("sharded train step OK", loss)
    """)
