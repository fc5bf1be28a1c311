import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Dry-run for the paper's own workload at production scale: distributed
PDX similarity search over the 16x16 / 2x16x16 mesh.

Corpus: 100M vectors x 1536 dims (OpenAI-embedding scale, ~614 GB f32 —
1.2 GB/chip block-sharded).  Query batch: 128.  Variants:

  block            — partitions sharded across chips; local scan + local
                     top-k + all-gather(k) merge  (baseline, paper-faithful
                     data parallelism)
  dim              — paper §7's dimension sharding: psum of partial
                     distances (collective-heavy, reads only local dims)
  block_matmul     — beyond-paper: batched queries via the MXU matmul form
  block_matmul_bf16— + bf16 storage (halves the memory term)
  block_matmul_int8— + int8 storage w/ per-partition scales (4x less HBM;
                     dequant fused into the tile read)
  block_pruned     — + ADSampling masked pruning before the merge

Each lowers+compiles and records the same JSON schema as dryrun.py, so the
roofline table treats the paper's workload as a first-class cell.
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ..core.topk import topk_init, topk_merge
from .analysis import collective_bytes_hlo, jaxpr_cost
from .mesh import make_production_mesh

N_VECTORS = 100_000_000
DIM = 1536
CAPACITY = 8192
QUERIES = 128
K = 10


def _scan_tiles_batched(data_l, ids_l, Q, k, metric_bf16=False):
    """(P_loc, D, C) x (B, D) -> per-shard TopK per query (matmul form)."""
    B = Q.shape[0]

    def body(state, inp):
        tile, tids = inp
        # int8 storage: dequantize on read (scale folded into the distance;
        # a real index stores per-partition scales — constant here since the
        # dry-run only measures structure)
        if tile.dtype == jnp.int8:
            tile_c = tile.astype(jnp.bfloat16) * jnp.bfloat16(0.02)
        elif metric_bf16:
            tile_c = tile.astype(jnp.bfloat16)
        else:
            tile_c = tile
        Qc = Q.astype(tile_c.dtype)
        cross = jax.lax.dot_general(
            Qc, tile_c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        qn = jnp.sum(Q.astype(jnp.float32) ** 2, axis=1, keepdims=True)
        xn = jnp.sum(
            tile_c.astype(jnp.float32) ** 2, axis=0, keepdims=True
        )
        d = qn - 2.0 * cross + xn
        state = jax.vmap(topk_merge, (0, 0, None))(state, d, tids)
        return state, None

    init = jax.vmap(lambda _: topk_init(k))(jnp.arange(B))
    state, _ = jax.lax.scan(body, init, (data_l, ids_l))
    return state


def build_pdx_cell(variant: str, mesh, dtype=jnp.float32):
    n_parts = N_VECTORS // CAPACITY  # 12207 -> pad to multiple of 256
    nd = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    n_parts = ((n_parts + nd - 1) // nd) * nd
    store_dtype = dtype
    if "bf16" in variant:
        store_dtype = jnp.bfloat16
    elif "int8" in variant:
        store_dtype = jnp.int8
    data = jax.ShapeDtypeStruct((n_parts, DIM, CAPACITY), store_dtype)
    ids = jax.ShapeDtypeStruct((n_parts, CAPACITY), jnp.int32)
    Q = jax.ShapeDtypeStruct((QUERIES, DIM), jnp.float32)
    shard_axes = tuple(mesh.axis_names)  # all axes shard the partition dim

    if variant.startswith("block"):
        pruned = "pruned" in variant
        matmul = "matmul" in variant

        def local(data_l, ids_l, Q_l):
            if matmul:
                st = _scan_tiles_batched(
                    data_l, ids_l, Q_l, K, metric_bf16="bf16" in variant
                )
            else:
                def one_q(q):
                    def body(state, inp):
                        tile, tids = inp
                        diff = tile.astype(jnp.float32) - q[:, None]
                        d = jnp.sum(diff * diff, axis=0)
                        if pruned:
                            # ADSampling-style mask on the first 64 dims
                            part = jnp.sum(diff[:64] * diff[:64], axis=0)
                            keep = part * (DIM / 64.0) <= (
                                topk_merge(state, d, tids).dists[-1]
                                * (1.0 + 2.1 / 8.0) ** 2
                            )
                            d = jnp.where(keep, d, jnp.inf)
                        return topk_merge(state, d, tids), None

                    st, _ = jax.lax.scan(body, topk_init(K), (data_l, ids_l))
                    return st

                st = jax.vmap(one_q)(Q_l)
            all_d = jax.lax.all_gather(st.dists, shard_axes)
            all_i = jax.lax.all_gather(st.ids, shard_axes)
            nrep = all_d.shape[0]
            merged = jax.vmap(
                lambda d, i: topk_merge(topk_init(K), d.reshape(-1), i.reshape(-1)),
                (1, 1),
            )(all_d.reshape(nrep, QUERIES, K), all_i.reshape(nrep, QUERIES, K))
            return merged.dists, merged.ids

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(shard_axes), P(shard_axes), P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn, (data, ids, Q), (
            NamedSharding(mesh, P(shard_axes)),
            NamedSharding(mesh, P(shard_axes)),
            NamedSharding(mesh, P()),
        )

    if variant == "dim":
        # dimensions sharded on 'model'; partitions on remaining axes
        daxes = tuple(a for a in mesh.axis_names if a != "model")

        def local_dim(data_l, ids_l, Q_l):
            def one_q(q_l):
                def body(acc_state, inp):
                    tile, tids = inp
                    diff = tile.astype(jnp.float32) - q_l[:, None]
                    partial = jnp.sum(diff * diff, axis=0)
                    total = jax.lax.psum(partial, "model")
                    return topk_merge(acc_state, total, tids), None

                st, _ = jax.lax.scan(body, topk_init(K), (data_l, ids_l))
                return st

            st = jax.vmap(one_q)(Q_l)  # queries share the dim shard
            all_d = jax.lax.all_gather(st.dists, daxes)
            all_i = jax.lax.all_gather(st.ids, daxes)
            nrep = all_d.shape[0]
            merged = jax.vmap(
                lambda d, i: topk_merge(topk_init(K), d.reshape(-1), i.reshape(-1)),
                (1, 1),
            )(all_d.reshape(nrep, QUERIES, K), all_i.reshape(nrep, QUERIES, K))
            return merged.dists, merged.ids

        fn = shard_map(
            local_dim, mesh=mesh,
            in_specs=(P(daxes, "model", None), P(daxes), P(None, "model")),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return fn, (data, ids, Q), (
            NamedSharding(mesh, P(daxes, "model", None)),
            NamedSharding(mesh, P(daxes)),
            NamedSharding(mesh, P(None, "model")),
        )

    raise ValueError(variant)


def run_variant(variant: str, mesh_name: str, out_dir: str) -> dict:
    rec = {"arch": f"pdx-search-{variant}", "shape": "batch128_100Mx1536",
           "mesh": mesh_name, "step": "search"}
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi_pod"))
    try:
        fn, args, shardings = build_pdx_cell(variant, mesh)
        jx = jax.make_jaxpr(fn)(*args)
        jcost = jaxpr_cost(jx)
        t0 = time.time()
        with mesh:
            lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
            compiled = lowered.compile()
        dt = time.time() - t0
        mem = compiled.memory_analysis()
        coll = collective_bytes_hlo(compiled.as_text())
        mem_rec = {}
        if mem is not None:
            for kk in ("argument_size_in_bytes", "temp_size_in_bytes",
                       "peak_memory_in_bytes"):
                v = getattr(mem, kk, None)
                if v is not None:
                    mem_rec[kk] = int(v)
        rec.update(
            status="ok", compile_s=round(dt, 2), jaxpr_cost=jcost,
            collectives=coll, memory=mem_rec,
            n_devices=int(mesh.devices.size),
            params_total=float(N_VECTORS) * DIM, params_active=float(N_VECTORS) * DIM,
            tokens=QUERIES,
        )
        print(f"[dryrun-pdx] {variant} x {mesh_name}: OK compile {dt:.1f}s "
              f"flops={jcost.get('flops', 0):.3e} coll={coll['total']:.3e}B")
        print(f"  memory: {mem_rec}")
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2500:])
        print(f"[dryrun-pdx] {variant} x {mesh_name}: FAIL {e}")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(
            out_dir, f"pdx-search-{variant}__batch128__{mesh_name}.json"
        ), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


VARIANTS = ["block", "dim", "block_matmul", "block_matmul_bf16",
            "block_matmul_int8", "block_pruned"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variant", default=None, choices=VARIANTS)
    ap.add_argument("--mesh", default="single_pod",
                    choices=["single_pod", "multi_pod", "both"])
    ap.add_argument("--out", default="results/dryrun_pdx")
    args = ap.parse_args()
    variants = [args.variant] if args.variant else VARIANTS
    meshes = ["single_pod", "multi_pod"] if args.mesh == "both" else [args.mesh]
    fails = 0
    for m in meshes:
        for v in variants:
            fails += run_variant(v, m, args.out)["status"] == "error"
    raise SystemExit(1 if fails else 0)


if __name__ == "__main__":
    main()
