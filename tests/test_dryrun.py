"""Dry-run machinery tests: mesh construction, analysis parsers, and one
real full-config 512-device lower+compile cell in a subprocess (slow)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mesh_shapes_are_lazy_and_correct():
    # importing must not init devices; calling builds the documented shapes
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert m1.axis_names == ("data", "model") and m1.devices.size == 256
        m2 = make_production_mesh(multi_pod=True)
        assert m2.axis_names == ("pod", "data", "model")
        assert m2.devices.size == 512
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr


def test_collective_parser_trip_counts():
    from repro.launch.analysis import collective_bytes_hlo

    hlo = textwrap.dedent("""
    HloModule test

    %body (p: (s32[], f32[64])) -> (s32[], f32[64]) {
      %p = (s32[], f32[64]) parameter(0)
      %g = f32[64]{0} get-tuple-element(%p), index=1
      %ar = f32[64]{0} all-reduce(%g), replica_groups={{0,1}}, to_apply=%sum
      ROOT %t = (s32[], f32[64]) tuple(%g, %ar)
    }

    %cond (p: (s32[], f32[64])) -> pred[] {
      %p = (s32[], f32[64]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %c = s32[] constant(7)
      ROOT %lt = pred[] compare(%i, %c), direction=LT
    }

    ENTRY %main (x: f32[64]) -> f32[64] {
      %x = f32[64]{0} parameter(0)
      %ag = f32[128]{0} all-gather(%x), dimensions={0}
      %w = (s32[], f32[64]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
      ROOT %r = f32[64]{0} get-tuple-element(%w), index=1
    }
    """)
    res = collective_bytes_hlo(hlo)
    assert res["bytes"]["all-gather"] == 128 * 4
    assert res["bytes"]["all-reduce"] == 64 * 4 * 7  # trip-multiplied
    assert res["count"]["all-reduce"] == 7


def test_jaxpr_cost_counts_attention_flops():
    import jax
    import jax.numpy as jnp

    from repro.launch.analysis import jaxpr_cost
    from repro.models.common import chunked_attention

    B, S, H, D = 2, 256, 4, 32
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.float32)
    jx = jax.make_jaxpr(
        lambda q, k, v: chunked_attention(q, k, v, q_chunk=128, kv_chunk=128)
    )(q, q, q)
    c = jaxpr_cost(jx)
    expect = 2 * 2 * B * H * S * S * D  # qk + pv
    assert 0.9 * expect <= c["dot_flops"] <= 1.6 * expect, (
        c["dot_flops"], expect,
    )


@pytest.mark.slow
def test_full_config_cell_compiles_on_512_devices(tmp_path):
    """qwen2-72b prefill_32k: full assigned dims, 16x16 mesh, ShapeDtype
    inputs, lower+compile must succeed (the fastest full cell, ~10s)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen2-72b",
         "--shape", "prefill_32k", "--mesh", "single_pod",
         "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=500, env=env, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.load(open(tmp_path / "qwen2-72b__prefill_32k__single_pod.json"))
    assert rec["status"] == "ok"
    assert rec["jaxpr_cost"]["flops"] > 1e15  # 32k prefill is heavy
    assert rec["memory"].get("peak_memory_in_bytes", 0) > 0
