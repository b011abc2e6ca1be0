"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: the TPU compiler is handed a v5e:2x2
topology that is described, not attached, and each test checks that the
kernel lowers to a native ``tpu_custom_call`` (not interpret mode).
Compiling is what the interpret-mode tests cannot show: tiling and
fast-memory limits are only enforced by the chip's compiler.

The topology is described inside a fixture and never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import hashlib
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import repair
from repro.core.circulant import CodeSpec
from repro.exec.plan import PlanCache, make_regen_fn
from repro.kernels import dispatch
from repro.kernels.circulant_encode import circulant_encode
from repro.kernels.gf_matmul import gf_matmul
from repro.sharding.mesh import StreamMesh

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)


def _native(compiled) -> str:
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"
    return text


@pytest.mark.parametrize("m,k,s", [(8, 4, 1 << 20), (2, 5, 1 << 18)])
def test_gf_matmul_compiles_for_v5e(chip, m, k, s):
    fn = jax.jit(gf_matmul, static_argnames=("p", "interpret"))
    _native(fn.lower(chip((m, k)), chip((k, s)), p=257,
                     interpret=False).compile())


@pytest.mark.parametrize("k", [4, 8])
def test_circulant_encode_compiles_for_v5e(chip, k):
    spec = CodeSpec.make(k, 257)
    _native(circulant_encode.lower(chip((spec.n, 1 << 20)), c=spec.c,
                                   p=257, interpret=False).compile())


def test_vmapped_fused_regeneration_compiles_for_v5e(chip):
    k, f, s = 4, 8, 1 << 18
    mm = dispatch.get("pallas").matmul
    _native(repair._fused_regenerate_vmapped.lower(
        mm, chip((2, k + 1)), chip((f, s)), chip((f, k, s)), p=257
    ).compile())


@pytest.mark.parametrize("op", ["circulant_encode", "regenerate"])
def test_byte_operand_plans_compile_for_v5e(topo, op):
    """The checkpointer's plans take uint8 data symbols and widen them
    to int32 on the chip ahead of the kernel, at the stream tile of a
    save and a restore."""
    one = SingleDeviceSharding(topo.devices[0])
    u8 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=one)
    i32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    backend, spec, s = dispatch.get("pallas"), CodeSpec.make(4, 257), 1 << 20
    if op == "circulant_encode":
        fn = lambda d: backend.circulant_encode(d.astype(jnp.int32),
                                                spec.c, 257)
        args = (u8((spec.n, s)),)
    else:
        regen = make_regen_fn(backend.matmul, 257)
        fn = lambda rm, rp, nd: regen(rm, rp, nd.astype(jnp.int32))
        args = (i32((2, spec.k + 1)), i32((s,)), u8((spec.k, s)))
    _native(jax.jit(fn).lower(*args).compile())


def test_sharded_encode_plan_compiles_over_four_v5e_chips(topo):
    """The planner's own sharded lowering over a 4-chip stream mesh: one
    kernel per shard and no cross-chip traffic (every op is
    column-local over the stream axis)."""
    spec = CodeSpec.make(4, 257)
    pc = PlanCache(dispatch.get("pallas"), 257,
                   mesh=StreamMesh(4, devices=topo.devices))
    compiled = pc._compile(
        "circulant_encode",
        lambda d: pc.backend.circulant_encode(d, spec.c, 257),
        ((spec.n, 4 << 18),))
    text = _native(compiled)
    assert not [c for c in COLLECTIVES if c in text]


@pytest.mark.parametrize("full_tracebacks", [True, False])
def test_kernel_lowering_and_call_site(chip, full_tracebacks):
    """A native kernel's lowering carries its MLIR locations, which JAX's
    persistent-cache key does not strip: with full tracebacks the same
    kernel lowers differently from another call site.  That is why
    ``enable_compile_cache`` keeps only the innermost frame."""
    def lower():
        jax.clear_caches()
        low = jax.jit(lambda a, b: gf_matmul(a, b, 257, interpret=False)
                      ).lower(chip((8, 4)), chip((4, 4096)))
        return hashlib.sha256(low.as_text().encode()).hexdigest()

    def from_elsewhere():
        return lower()

    prev = jax.config.jax_include_full_tracebacks_in_locations
    jax.config.update("jax_include_full_tracebacks_in_locations",
                      full_tracebacks)
    try:
        here, there = lower(), from_elsewhere()
    finally:
        jax.config.update("jax_include_full_tracebacks_in_locations", prev)
    assert (here != there) == full_tracebacks
