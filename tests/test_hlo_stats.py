"""Trip-count-aware HLO analyzer: validated against analytic FLOP counts of
known programs and a crafted HLO module."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_stats


def compile_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_matmul_flops_trip_aware():
    """5-iteration scan of a 128^3 matmul: analytic = 5 * 2 * 128^3."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def fn(a):
        def body(c, _):
            return c @ a, None
        out, _ = jax.lax.scan(body, a, None, length=5)
        return out

    txt = compile_text(fn, x)
    r = hlo_stats.analyze(txt)
    want = 5 * 2 * 128**3
    assert want * 0.8 <= r["flops"] <= want * 1.6, (r["flops"], want)


def test_nested_scan_multiplies_trip_counts():
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)

    def fn(a):
        def outer(c, _):
            def inner(ci, _):
                return ci @ a, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        out, _ = jax.lax.scan(outer, a, None, length=4)
        return out

    txt = compile_text(fn, x)
    r = hlo_stats.analyze(txt)
    want = 12 * 2 * 64**3
    assert want * 0.8 <= r["flops"] <= want * 1.8, (r["flops"], want)


def test_no_loop_matmul_counted_once():
    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    txt = compile_text(lambda a: a @ a, x)
    r = hlo_stats.analyze(txt)
    want = 2 * 256**3
    assert want * 0.9 <= r["flops"] <= want * 1.3, (r["flops"], want)


def test_crafted_collectives_and_symbols():
    hlo = """HloModule test, entry_computation_layout={()->f32[]}

%cond (p: (s32[], f32[64])) -> pred[] {
  %p = (s32[], f32[64]) parameter(0)
  %gte = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(7)
  ROOT %cmp = pred[] compare(%gte, %c), direction=LT
}

%body (p2: (s32[], f32[64])) -> (s32[], f32[64]) {
  %p2 = (s32[], f32[64]) parameter(0)
  %g0 = s32[] get-tuple-element(%p2), index=0
  %g1 = f32[64]{0} get-tuple-element(%p2), index=1
  %ar = f32[64]{0} all-reduce(%g1), replica_groups={}, to_apply=%sum
  %one = s32[] constant(1)
  %next = s32[] add(%g0, %one)
  ROOT %t = (s32[], f32[64]) tuple(%next, %ar)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (x: f32[64]) -> (s32[], f32[64]) {
  %x = f32[64]{0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[64]) tuple(%z, %x)
  ROOT %w = (s32[], f32[64]) while(%init), condition=%cond, body=%body
}
"""
    r = hlo_stats.analyze(hlo)
    assert r["entry"] == "main"
    # 7 loop iterations x one 64-float all-reduce
    ar = r["collectives"]["all-reduce"]
    assert ar["count"] == 7, ar
    assert ar["bytes"] == 7 * 64 * 4, ar


def test_parse_collectives_symbol_table():
    from repro.launch.dryrun import parse_collectives
    hlo = """HloModule m

ENTRY %main (x: f32[128]) -> f32[128] {
  %x = f32[128]{0} parameter(0)
  %y = f32[128]{0} add(%x, %x)
  %ag = f32[512]{0} all-gather(%y), dimensions={0}
  %rs = f32[128]{0} reduce-scatter(%ag), dimensions={0}, to_apply=%s
  ROOT %out = f32[128]{0} all-reduce(%rs), to_apply=%s
}
"""
    c = parse_collectives(hlo)
    assert c["all-gather"]["count"] == 1
    assert c["all-gather"]["bytes"] == 128 * 4        # operand
    assert c["all-gather"]["result_bytes"] == 512 * 4
    assert c["reduce-scatter"]["bytes"] == 512 * 4
    assert c["all-reduce"]["bytes"] == 128 * 4


def _check_dryrun_record(rec: dict, name: str) -> None:
    assert "error" not in rec, name
    assert rec["dynamic"]["flops"] >= rec["cost"]["flops"] * 0.5, name
    if rec["kind"] == "train":
        # trip-aware flops must exceed 6ND/chips (bwd+remat overhead)
        model = 6 * rec["n_active_params"] * rec["tokens_per_step"] / rec["n_devices"]
        assert rec["dynamic"]["flops"] > 0.5 * model, name


def test_dryrun_artifacts_consistency():
    """End-to-end dry-run smoke: lower a REDUCED train cell on a forced
    8-device (4 data x 2 model) mesh in a subprocess and assert the
    artifact invariants on the result — so the checks run in every CI
    pass instead of skipping when the 512-chip matrix hasn't been
    produced.  Real artifacts, when present, are held to the same bar.
    """
    import json
    import os
    import pathlib
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import json, os, sys
        os.environ["REPRO_DRYRUN_DEVICES"] = "8"
        from repro.launch import dryrun          # sets XLA_FLAGS pre-jax
        import jax
        assert len(jax.devices()) == 8, jax.devices()
        from repro.configs import get_config
        from repro.configs.base import ShapeConfig
        cfg = get_config("qwen3-4b").reduced(
            n_layers=2, d_model=32, n_heads=4, n_kv_heads=2, head_dim=8,
            d_ff=64, vocab_size=256, loss_chunk=16)
        shape = ShapeConfig("train_smoke", 64, 8, "train")
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        rec = dryrun.lower_cell("qwen3-4b", "train_smoke",
                                cfg=cfg, shape=shape, mesh=mesh)
        json.dump(rec, sys.stdout)
    """)
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=480)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    rec = json.loads(res.stdout)
    assert rec["n_devices"] == 8
    assert rec["mesh"] == "mesh4x2"
    assert rec["collectives"]["total_bytes"] > 0   # model axis => collectives
    _check_dryrun_record(rec, "train_smoke")
    # any committed full-scale artifacts must hold the same invariants
    res_dir = pathlib.Path(__file__).resolve().parent.parent / "benchmarks" / "dryrun_results"
    for f in res_dir.glob("*.json"):
        if f.name.endswith(".error.json"):
            continue
        _check_dryrun_record(json.loads(f.read_text()), f.name)
