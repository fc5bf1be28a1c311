"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (
    batched_distance_op,
    batched_distance_quant_op,
    nary_distance_op,
    pdx_distance_op,
    pdx_prune_scan_multi_op,
    pdx_prune_scan_op,
)

SHAPES = [(8, 64), (96, 128), (128, 1000), (384, 96), (33, 130)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-1) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("metric", ["l2", "ip", "l1"])
@pytest.mark.parametrize("D,V", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pdx_distance_kernel(metric, D, V, dtype, rng):
    T = jnp.asarray(rng.standard_normal((D, V)), dtype)
    q = jnp.asarray(rng.standard_normal(D), dtype)
    got = pdx_distance_op(T, q, metric)
    want = ref.pdx_distance_ref(T, q, metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("metric", ["l2", "ip", "l1"])
@pytest.mark.parametrize("N,D", [(64, 8), (1000, 128), (130, 33)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_nary_distance_kernel(metric, N, D, dtype, rng):
    X = jnp.asarray(rng.standard_normal((N, D)), dtype)
    q = jnp.asarray(rng.standard_normal(D), dtype)
    got = nary_distance_op(X, q, metric)
    want = ref.nary_distance_ref(X, q, metric)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(dtype))


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("B,D,V", [(4, 32, 64), (16, 128, 256), (3, 50, 130)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_batched_distance_kernel(metric, B, D, V, dtype, rng):
    T = jnp.asarray(rng.standard_normal((D, V)), dtype)
    Q = jnp.asarray(rng.standard_normal((B, D)), dtype)
    got = batched_distance_op(T, Q, metric)
    want = ref.batched_distance_ref(T, Q, metric)
    tol = dict(rtol=3e-2, atol=5e-1) if dtype == jnp.bfloat16 else dict(rtol=1e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


@pytest.mark.parametrize("D,V", [(64, 128), (128, 256), (96, 1000)])
@pytest.mark.parametrize("d_tile", [16, 32, 64])
def test_prune_scan_kernel_matches_ref(D, V, d_tile, rng):
    T = jnp.asarray(rng.standard_normal((D, V)), jnp.float32)
    q = jnp.asarray(rng.standard_normal(D), jnp.float32)
    # threshold near the 10th smallest distance so pruning actually happens
    full = np.asarray(ref.pdx_distance_ref(T, q))
    thr = jnp.float32(np.partition(full, 10)[10])
    got_d, got_a = pdx_prune_scan_op(T, q, thr, eps0=2.1, d_tile=d_tile)
    want_d, want_a = ref.pdx_prune_scan_ref(T, q, thr, d_tile=d_tile, eps0=2.1)
    np.testing.assert_allclose(np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))


def test_prune_scan_never_prunes_nearest(rng):
    """Survivors must include the true nearest neighbour at sane eps0."""
    D, V = 128, 512
    T = jnp.asarray(rng.standard_normal((D, V)), jnp.float32)
    q = jnp.asarray(rng.standard_normal(D), jnp.float32)
    full = np.asarray(ref.pdx_distance_ref(T, q))
    thr = jnp.float32(np.partition(full, 10)[10])
    _, alive = pdx_prune_scan_op(T, q, thr, eps0=2.1)
    assert np.asarray(alive)[int(np.argmin(full))] == 1.0


def test_prune_scan_all_pruned_when_thr_zero(rng):
    D, V = 64, 256
    T = jnp.asarray(rng.standard_normal((D, V)) + 10.0, jnp.float32)
    q = jnp.asarray(np.zeros(D), jnp.float32)
    _, alive = pdx_prune_scan_op(T, q, jnp.float32(1e-3))
    assert np.asarray(alive).sum() == 0.0


def test_prune_scan_returns_bool_and_masks_pad_lanes(rng):
    """Satellite: alive is a bool mask (not the kernel's f32 encoding) and
    lanes whose ids are -1 (PAD columns) can never surface as survivors —
    even with an infinite threshold that keeps everything else alive."""
    D, V = 64, 130
    T = jnp.asarray(rng.standard_normal((D, V)), jnp.float32)
    q = jnp.asarray(rng.standard_normal(D), jnp.float32)
    ids = np.arange(V, dtype=np.int32)
    ids[5] = -1
    ids[-3:] = -1
    _, alive = pdx_prune_scan_op(T, q, jnp.float32(np.inf), jnp.asarray(ids))
    alive = np.asarray(alive)
    assert alive.dtype == np.bool_
    assert not alive[ids < 0].any()
    assert alive[ids >= 0].all()


# ---------------------------------------------------------------- megakernel
MULTI_SHAPES = [(2, 64, 128), (3, 50, 130), (4, 96, 1000)]


def _quantize(T, rng):
    """Per-dimension affine int8, exact-range (mirrors the layout policy)."""
    offset = T.mean(axis=(0, 2))
    dev = np.abs(T - offset[None, :, None]).max(axis=(0, 2))
    scale = np.maximum(dev, 1e-6) / 127.0
    q = np.clip(np.round((T - offset[None, :, None]) / scale[None, :, None]),
                -127, 127).astype(np.int8)
    return q, scale.astype(np.float32), offset.astype(np.float32)


@pytest.mark.parametrize("P,D,V", MULTI_SHAPES)
@pytest.mark.parametrize("use_pallas", [True, False])
def test_prune_scan_multi_matches_ref(P, D, V, use_pallas, rng):
    """Megakernel vs oracle at non-aligned D/V with PAD lanes, both bodies."""
    T = jnp.asarray(rng.standard_normal((P, D, V)), jnp.float32)
    q = jnp.asarray(rng.standard_normal(D), jnp.float32)
    ids = rng.integers(0, 10_000, (P, V)).astype(np.int32)
    ids[:, -7:] = -1
    ids[0, 3] = -1
    full = np.asarray(ref.pdx_distance_ref(T[1], q))
    thr = jnp.float32(np.partition(full, 10)[10])
    got_d, got_a = pdx_prune_scan_multi_op(
        T, jnp.asarray(ids), q, thr, use_pallas=use_pallas
    )
    want_d, want_a = ref.pdx_prune_scan_multi_ref(
        T, jnp.asarray(ids), q, thr, d_tile=min(64, D), eps0=2.1
    )
    assert np.asarray(got_a).dtype == np.bool_
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a) != 0)
    assert not np.asarray(got_a)[ids < 0].any()  # PAD lanes never survive


@pytest.mark.parametrize("use_pallas", [True, False])
def test_prune_scan_multi_quantized_operands(use_pallas, rng):
    """int8 operands dequantize in-register; bf16 casts — both match the
    oracle run on the same quantized values."""
    P, D, V = 3, 96, 130
    T = rng.standard_normal((P, D, V)).astype(np.float32)
    q = jnp.asarray(rng.standard_normal(D), jnp.float32)
    ids = rng.integers(0, 10_000, (P, V)).astype(np.int32)
    ids[:, -5:] = -1
    thr = jnp.float32(np.partition(
        np.asarray(ref.pdx_distance_ref(jnp.asarray(T[0]), q)), 10)[10])

    Tq, scale, offset = _quantize(T, rng)
    got_d, got_a = pdx_prune_scan_multi_op(
        jnp.asarray(Tq), jnp.asarray(ids), q, thr,
        jnp.asarray(scale), jnp.asarray(offset), use_pallas=use_pallas,
    )
    want_d, want_a = ref.pdx_prune_scan_multi_ref(
        jnp.asarray(Tq), jnp.asarray(ids), q, thr, d_tile=64, eps0=2.1,
        scale=jnp.asarray(scale), offset=jnp.asarray(offset),
    )
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a) != 0)

    Tb = jnp.asarray(T, jnp.bfloat16)
    got_d, got_a = pdx_prune_scan_multi_op(
        Tb, jnp.asarray(ids), q, thr, use_pallas=use_pallas
    )
    want_d, want_a = ref.pdx_prune_scan_multi_ref(
        Tb, jnp.asarray(ids), q, thr, d_tile=64, eps0=2.1
    )
    np.testing.assert_allclose(
        np.asarray(got_d), np.asarray(want_d), rtol=1e-4, atol=1e-4
    )
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a) != 0)


@pytest.mark.parametrize("metric", ["l2", "ip"])
@pytest.mark.parametrize("B,D,V", [(4, 32, 64), (3, 50, 130)])
@pytest.mark.parametrize("use_pallas", [True, False])
def test_batched_distance_quant_kernel(metric, B, D, V, use_pallas, rng):
    T = rng.standard_normal((1, D, V)).astype(np.float32)
    Q = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
    Tq, scale, offset = _quantize(T, rng)
    got = batched_distance_quant_op(
        jnp.asarray(Tq[0]), Q, jnp.asarray(scale), jnp.asarray(offset),
        metric, use_pallas,
    )
    want = ref.batched_distance_quant_ref(
        jnp.asarray(Tq[0]), Q, jnp.asarray(scale), jnp.asarray(offset),
        metric,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-3, atol=1e-3
    )
    # bf16 operands without dequant vectors
    Tb = jnp.asarray(T[0], jnp.bfloat16)
    got = batched_distance_quant_op(Tb, Q, metric=metric,
                                    use_pallas=use_pallas)
    want = ref.batched_distance_quant_ref(Tb, Q, metric=metric)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=3e-2, atol=5e-1
    )


@pytest.mark.parametrize("use_pallas", [True, False])
def test_prune_scan_prefetch_dtile_skip(use_pallas, rng):
    """The prefetch-skip wrapper returns (dists, alive, streamed) matching
    the d-skip oracle on both bodies; entry-dead partitions stream zero
    tiles, partitions whose last lane dies mid-scan stop at that tile, and
    the realized d-tile byte model never exceeds (and here strictly
    undercuts) the partition-granular model."""
    from repro.kernels.ops import pdx_prune_scan_multi_prefetch_op

    P, D, V = 5, 200, 130
    T = rng.standard_normal((P, D, V)).astype(np.float32)
    # partition 0 is near the query (survives), the rest drift further out
    # so whole partitions and individual lanes die at varying tiles
    q = T[0, :, 3] + rng.standard_normal(D).astype(np.float32) * 0.01
    for p in range(1, P):
        T[p] += p * 0.8
    ids = rng.integers(0, 10_000, (P, V)).astype(np.int32)
    ids[:, -7:] = -1
    ids[4] = -1  # entry-dead partition: must stream nothing
    full = np.asarray(ref.pdx_distance_ref(jnp.asarray(T[0]), jnp.asarray(q)))
    thr = jnp.float32(np.partition(full, 10)[10])
    got_d, got_a, got_s = pdx_prune_scan_multi_prefetch_op(
        jnp.asarray(T), jnp.asarray(ids), jnp.asarray(q), thr,
        use_pallas=use_pallas,
    )
    want_d, want_a, want_s = ref.pdx_prune_scan_multi_dskip_ref(
        jnp.asarray(T), jnp.asarray(ids), jnp.asarray(q), thr,
        d_tile=64, eps0=2.1,
    )
    np.testing.assert_allclose(
        np.asarray(got_d)[np.asarray(got_a)],
        np.asarray(want_d)[np.asarray(got_a)], rtol=1e-4, atol=1e-4,
    )
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a) != 0)
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    s = np.asarray(got_s)
    assert s[4] == 0.0
    n_tiles = -(-D // 64)
    dtile_bytes = np.minimum(s * 64, D).sum() * V * 4
    part_bytes = (s > 0).sum() * D * V * 4
    assert dtile_bytes <= part_bytes
    # the drifted partitions die mid-scan: the d-tile model must realize
    # a strict saving over partition-granular skip on this data
    assert (s[(s > 0)] < n_tiles).any()
    assert dtile_bytes < part_bytes


def test_kernels_interpret_only_on_cpu(monkeypatch):
    import jax

    from repro.kernels import _backend

    assert _backend.interpret_mode() is True  # this suite runs on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _backend.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        _backend.interpret_mode()
