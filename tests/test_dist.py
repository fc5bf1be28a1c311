"""Distributed substrate tests — run in subprocesses with 8 fake CPU devices
(XLA_FLAGS device-count forcing is process-global, so it must not leak into
this test process; see conftest note)."""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_devices(body: str, n: int = 8) -> str:
    code = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import jax, numpy as np, jax.numpy as jnp
        assert jax.device_count() == {n}, jax.devices()
        """
    ) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # the child fakes CPU devices; it must never reach for a chip the
    # parent may hold
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, env=env,
    )
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_block_sharded_search_matches_single_device():
    run_devices("""
    from jax.sharding import Mesh
    from repro.core.layout import build_flat_store
    from repro.core.pdxearch import search_batch_matmul
    from repro.dist.pdx_sharded import search_block_sharded
    from repro.data.synthetic import make_dataset, ground_truth

    X, Q = make_dataset(2048, 32, "normal", n_queries=2, seed=0)
    store = build_flat_store(X, capacity=128)  # 16 partitions -> 2/device
    mesh = jax.make_mesh((8,), ("data",))
    gt_ids, gt_d = ground_truth(X, Q, k=5)
    for qi, q in enumerate(Q):
        res = search_block_sharded(mesh, store.data, store.ids, jnp.asarray(q), 5)
        np.testing.assert_allclose(np.sort(np.asarray(res.dists)),
                                   np.sort(gt_d[qi]), rtol=1e-4)
    print("OK")
    """)


def test_dim_sharded_search_matches_single_device():
    run_devices("""
    from jax.sharding import Mesh
    from repro.core.layout import build_flat_store
    from repro.dist.pdx_sharded import search_dim_sharded
    from repro.data.synthetic import make_dataset, ground_truth

    X, Q = make_dataset(1024, 64, "skewed", n_queries=2, seed=1)  # D=64 /8
    store = build_flat_store(X, capacity=256)
    mesh = jax.make_mesh((8,), ("model",))
    gt_ids, gt_d = ground_truth(X, Q, k=5)
    for qi, q in enumerate(Q):
        res = search_dim_sharded(mesh, store.data, store.ids, jnp.asarray(q), 5)
        np.testing.assert_allclose(np.sort(np.asarray(res.dists)),
                                   np.sort(gt_d[qi]), rtol=1e-4)
    print("OK")
    """)


def test_pipeline_parallel_matches_sequential():
    run_devices("""
    from jax.sharding import Mesh
    from repro.dist.pipeline import pipeline_apply

    n_stages, n_micro, mb, d = 8, 6, 4, 16
    mesh = jax.make_mesh((8,), ("stage",))
    key = jax.random.key(0)
    ws = jax.random.normal(key, (n_stages, d, d)) * 0.3

    def stage_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.key(1), (n_micro, mb, d))
    got = pipeline_apply(mesh, stage_fn, ws, x)
    want = x
    for s in range(n_stages):
        want = jnp.tanh(want @ ws[s])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    print("OK")
    """)


def test_compressed_psum_dp_grads():
    run_devices("""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from repro.train.compression import compressed_psum

    mesh = jax.make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.key(0), (8, 64)) * 0.01

    def local(gl):
        return compressed_psum({"g": gl[0]}, "data")["g"]

    fn = shard_map(local, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                   check_vma=False)
    got = np.asarray(jax.jit(fn)(g))
    want = np.asarray(jnp.mean(g, axis=0))
    err = np.abs(got - want).max()
    scale = float(jnp.abs(g).max()) / 127.0
    assert err <= scale * 1.5 + 1e-7, (err, scale)
    print("OK")
    """)


def test_gspmd_train_step_8dev_fsdp_tp():
    """End-to-end: tiny model, (2,4) data x model mesh, sharded params+batch,
    one jitted train step under GSPMD — the mini version of the dry-run."""
    run_devices("""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models.lm import build_model
    from repro.train.trainer import TrainConfig, make_train_step
    from repro.train.optimizer import OptConfig, opt_init
    from repro.dist.sharding import param_shardings, batch_shardings
    from repro.data.pipeline import TokenStream

    cfg = get_config("llama3.2-3b").reduced()
    model = build_model(cfg)
    # GSPMD: Auto axes (jax.make_mesh defaults to Explicit sharding-in-types)
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    params = model.init(jax.random.key(0))
    oc = OptConfig(warmup_steps=0)
    opt = opt_init(params, oc)
    ps = param_shardings(params, mesh, cfg)
    params = jax.device_put(params, ps)
    opt = jax.device_put(opt, jax.tree.map(
        lambda s: s, {"mu": ps, "nu": ps,
                      "step": NamedSharding(mesh, P())}))
    stream = TokenStream(cfg, 16, 4, seed=0)
    b = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}
    b = jax.device_put(b, batch_shardings(b, mesh))
    step = jax.jit(make_train_step(model, TrainConfig(opt=oc)))
    p2, o2, m = step(params, opt, b)
    assert np.isfinite(float(m["loss"]))
    print("OK", float(m["loss"]))
    """)


def test_elastic_checkpoint_restore_onto_mesh(tmp_path):
    """Save on 1 device -> restore sharded onto an 8-device mesh."""
    run_devices(f"""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import checkpoint as ckpt

    tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
    root = r"{tmp_path}/ck"
    ckpt.save(root, 1, tree)
    mesh = jax.make_mesh((8,), ("data",))
    sh = {{"w": NamedSharding(mesh, P("data", None))}}
    step, restored = ckpt.restore(root, tree, shardings=sh)
    assert step == 1
    assert restored["w"].sharding.is_equivalent_to(sh["w"], 2)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))
    print("OK")
    """)
