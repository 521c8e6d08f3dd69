"""Chip-compiler checks: every Pallas kernel compiles for a TPU v5e.

Nothing runs here.  The installed TPU compiler compiles each kernel (and
the sharded sweep engine on a 2x2 mesh) for a *described* v5e:2x2
topology, so a kernel that only passes in interpret mode fails here
instead of on the chip.  The topology is described inside a fixture:
only the worker that runs this file loads the TPU library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import transient
from repro.kernels import ops
from repro.kernels.rc_transient import rc_multistep_pallas
from repro.kernels.row_cycle import N_PARAMS, row_cycle_fused_pallas
from repro.kernels.strap_gather import strap_attend_pallas

B, N = transient.DEFAULT_B_CHUNK, 6
# a full chunk (two (8, 128) blocks) and one B_ALIGN batch (one (1, 128)
# tile: the service and nominal-tRC path)
ENGINE_ROWS = (B, transient.B_ALIGN)
STEPS = (transient.N_ACT_STEPS, transient.N_RESTORE_STEPS,
         transient.N_PRE_STEPS)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # described-chip executables cannot be read back without a chip
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    """Steer `backend="auto"` to the Pallas branch while tracing for the
    described chip; traces cached under the CPU branch are dropped."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    yield
    jax.clear_caches()


def shape(sharding, *dims, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)


def engine_operands(sharding, b=B):
    return [shape(sharding, b, w) for w in (N, N - 1, N, N, N, N_PARAMS)]


def assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b", ENGINE_ROWS)
def test_row_cycle_kernel_compiles(one_chip, b):
    fn = jax.jit(functools.partial(
        row_cycle_fused_pallas, dt=transient.DT_NS, n_act=STEPS[0],
        n_res=STEPS[1], n_pre=STEPS[2], interpret=False))
    assert_kernel(fn.lower(*engine_operands(one_chip, b=b)).compile())


def test_auto_backend_dispatches_compiled_kernel(one_chip, on_tpu):
    """`ops.row_cycle_fused(backend="auto")` on a TPU is the compiled
    kernel, never the interpreter or the oracle."""
    lowered = ops.row_cycle_fused.lower(
        *engine_operands(one_chip), transient.DT_NS, *STEPS, backend="auto")
    assert_kernel(lowered.compile())


@pytest.mark.parametrize("b", ENGINE_ROWS)
def test_row_cycle_kernel_counts_block_steps_on_chip(one_chip, on_tpu, b):
    """The kernel's third output on the chip: one int32 step count per
    block of `ops.row_cycle_block_rows` rows, from the custom call named
    `row_cycle_fused` (the op the benchmark's trace readers match)."""
    lowered = ops.row_cycle_fused.lower(
        *engine_operands(one_chip, b=b), transient.DT_NS, *STEPS,
        backend="auto")
    steps = lowered.out_info.block_steps
    block = ops.row_cycle_block_rows(b)
    assert block == min(1024, -(-b // 128) * 128)
    assert steps.shape == (-(-b // block),) and steps.dtype == jnp.int32
    text = lowered.compile().as_text()
    kernels = [ln.split(" = ", 1)[0].strip() for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all(k.startswith("%row_cycle_fused") for k in kernels)


def test_sharded_engine_compiles_on_2x2(topo, on_tpu):
    from jax.sharding import Mesh

    from repro.launch import shard
    mesh = Mesh(topo.devices, ("batch",))
    rows = shard.sweep_sharding(mesh)
    # two chunks per device: exercises the in-device lax.map over chunks
    engine = shard._sharded_engine(mesh, "auto", B)
    compiled = engine.lower(*engine_operands(rows, b=4 * 2 * B)).compile()
    assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_strap_attend_compiles_gqa_bf16(one_chip):
    b, hq, hkv, d, page, g, p = 8, 12, 2, 128, 16, 4, 64
    fn = jax.jit(functools.partial(strap_attend_pallas, pages_per_strap=g,
                                   interpret=False))
    kv = shape(one_chip, b, p, page, hkv, d, dtype=jnp.bfloat16)
    compiled = fn.lower(
        shape(one_chip, b, hq, d, dtype=jnp.bfloat16), kv, kv,
        shape(one_chip, b, p // g, dtype=jnp.int32),
        lengths=shape(one_chip, b, dtype=jnp.int32)).compile()
    assert_kernel(compiled)


def test_rc_multistep_compiles(one_chip):
    t = transient.N_RESTORE_STEPS
    fn = jax.jit(functools.partial(rc_multistep_pallas, dt=transient.DT_NS,
                                   interpret=False))
    compiled = fn.lower(shape(one_chip, B, N), shape(one_chip, B, N - 1),
                        shape(one_chip, B, N), shape(one_chip, B, N),
                        shape(one_chip, B, N), shape(one_chip, t)).compile()
    assert_kernel(compiled)
