"""Main-path Pallas kernels compile for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one kernel wrapper at serving
widths (D = 1536, the text-embedding width, and D = 96, a width that pads)
for one chip of a ``v5e:2x2`` topology the installed TPU compiler describes
without hardware.  The compiler refuses here what it would refuse on the
chip: block shapes off the (8, 128) tiling, relayouts Mosaic cannot do, more
VMEM than a kernel may use.  Interpret mode can show none of these.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import _backend
from repro.kernels.ops import (
    batched_cascade_stage_op,
    batched_distance_quant_op,
    pdx_prune_scan_multi_op,
    pdx_prune_scan_multi_prefetch_op,
)

P_PARTS, C = 64, 1024          # partitions x lanes: a 64k-vector store
B = 8                          # query batch of the batched kernels

# scan dtype -> (stored dtype, packed int4, has dequant scale/offset)
SCAN_DTYPES = {
    "f32": (jnp.float32, False, False),
    "bf16": (jnp.bfloat16, False, False),
    "int8": (jnp.int8, False, True),
    "int4": (jnp.uint8, True, True),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def compiled(monkeypatch, one_chip, no_compile_cache):
    """Kernels lower for the chip, not the interpreter.  The trace caches
    are cleared on both sides so no interpreted trace of a CPU test is
    reused here, and no chip trace leaks into a later CPU test."""
    monkeypatch.setattr(_backend, "interpret_mode", lambda: False)
    jax.clear_caches()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    yield spec
    jax.clear_caches()


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _dequant(spec, D, has_scale):
    if not has_scale:
        return None, None
    return spec((D,), jnp.float32), spec((D,), jnp.float32)


@pytest.mark.parametrize("D", [1536, 96])
@pytest.mark.parametrize("scan_dtype", list(SCAN_DTYPES))
@pytest.mark.parametrize(
    "op", [pdx_prune_scan_multi_op, pdx_prune_scan_multi_prefetch_op],
    ids=["multi", "multi_prefetch"],
)
def test_fused_scan_compiles(compiled, op, scan_dtype, D):
    dtype, packed, has_scale = SCAN_DTYPES[scan_dtype]
    rows = (D + 1) // 2 if packed else D
    scale, offset = _dequant(compiled, D, has_scale)

    def scan(T, ids, q, thr, scale, offset):
        return op(T, ids, q, thr, scale, offset, packed=packed,
                  dim=D if packed else None)

    _assert_kernel_compiles(
        scan,
        compiled((P_PARTS, rows, C), dtype),
        compiled((P_PARTS, C), jnp.int32),
        compiled((D,), jnp.float32),
        compiled((), jnp.float32),
        scale, offset,
    )


@pytest.mark.parametrize("D", [1536, 96])
@pytest.mark.parametrize("scan_dtype", ["bf16", "int8"])
def test_batched_distance_quant_compiles(compiled, scan_dtype, D):
    dtype, _, has_scale = SCAN_DTYPES[scan_dtype]
    scale, offset = _dequant(compiled, D, has_scale)
    _assert_kernel_compiles(
        batched_distance_quant_op,
        compiled((D, C), dtype),
        compiled((B, D), jnp.float32),
        scale, offset,
    )


@pytest.mark.parametrize("D", [1536, 96])
def test_batched_cascade_stage_compiles(compiled, D):
    S = 4 * C  # compacted survivor columns
    scale, offset = _dequant(compiled, D, True)
    _assert_kernel_compiles(
        batched_cascade_stage_op,
        compiled((D, S), jnp.int8),
        compiled((B, S), jnp.bool_),
        compiled((B, D), jnp.float32),
        compiled((B,), jnp.float32),
        scale, offset,
    )


@pytest.mark.parametrize("D", [1536, 96])
def test_fused_batch_scan_compiles(compiled, D):
    """The whole fused-batch program: the kernel, the slot-wise top-R
    scan, and the per-step merge it falls back to under ``lax.cond``."""
    from repro.core.plan import _fused_batch_scan

    scale, offset = _dequant(compiled, D, True)
    _assert_kernel_compiles(
        functools.partial(_fused_batch_scan, rk=40, metric="l2",
                          use_pallas=True, quantized=True),
        compiled((P_PARTS, D, C), jnp.int8),
        compiled((P_PARTS, C), jnp.int32),
        compiled((B, D), jnp.float32),
        scale, offset,
    )
