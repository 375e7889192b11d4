"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each runnable cell (see configs.shapes.skip_reason) this driver:
  1. builds the production mesh (16,16) or (2,16,16),
  2. lowers the right step function with full-size ShapeDtypeStruct
     inputs and the profile's in/out shardings,
  3. compiles (proving the distribution config is coherent),
  4. records memory_analysis / cost_analysis / the trip-count-aware HLO
     roofline terms (utils/hlo.py) into experiments/dryrun/*.json.

Usage:
  python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both
  python -m repro.launch.dryrun --list

Run as a script, it fabricates 512 host devices through XLA_FLAGS before
JAX starts a backend. Importing the module sets nothing, so no importer
(and no chip path) inherits the flag.
"""
import os

if __name__ == "__main__":
    # must precede the backend's start: jax fixes the device count then
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get, input_specs, skip_reason
from ..configs.shapes import SHAPES
from ..models import Model
from ..models.common import DP
from ..train.optimizer import AdamWConfig, init_opt_state, opt_state_spec
from ..train.step import TrainState, make_train_step
from ..utils.hlo import analyze_hlo
from ..utils.roofline import roofline_terms, model_flops_estimate
from .mesh import make_production_mesh, sharding_for

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def dp_divides(mesh, global_batch: int) -> bool:
    import math

    dp_size = math.prod(
        mesh.shape[a] for a in mesh.axis_names if a in ("pod", "data")
    )
    return global_batch % dp_size == 0


def batch_sharding(mesh, specs, cfg, shard_batch=True):
    dp = DP(mesh.axis_names) if shard_batch else None
    out = {}
    for k, s in specs.items():
        out[k] = NamedSharding(mesh, P(dp, *([None] * (len(s.shape) - 1))))
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, loss_chunk=512, attn_chunk=None,
             extra_tag: str = "", decode_shard_seq=True, remat=None):
    from dataclasses import replace
    cell = SHAPES[shape]
    cfg = get(arch)
    if attn_chunk is not None:
        cfg = replace(cfg, attn_chunk=attn_chunk)
    if remat is not None:
        cfg = replace(cfg, remat=remat)
    reason = skip_reason(cfg, cell)
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "step": cell.step,
        "tag": extra_tag, "ok": False,
    }
    if reason is not None:
        rec.update({"skipped": True, "reason": reason})
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    n_dev = mesh.devices.size
    train = cell.step == "train"
    if cfg.moe is not None:
        import math
        from dataclasses import replace
        dp_size = math.prod(mesh.shape[a] for a in mesh.axis_names if a in ("pod", "data"))
        cfg = replace(cfg, moe_dispatch_groups=dp_size)
    model = Model(cfg, mesh_axes=mesh.axis_names, fsdp=train)
    specs = input_specs(cfg, cell)
    shard_batch = dp_divides(mesh, cell.global_batch)
    in_batch_shard = batch_sharding(mesh, specs, cfg, shard_batch=shard_batch)

    t0 = time.perf_counter()
    with mesh:
        if cell.step == "train":
            opt_cfg = AdamWConfig()
            step_fn = make_train_step(model, opt_cfg, loss_chunk=loss_chunk)
            pspec = model.param_spec()
            state_shard = TrainState(
                params=sharding_for(mesh, pspec),
                opt=sharding_for(mesh, opt_state_spec(pspec)),
                step=NamedSharding(mesh, P()),
            )
            aparams = model.abstract_params()
            abstract_state = TrainState(
                params=aparams,
                opt=jax.eval_shape(init_opt_state, aparams),
                step=jax.ShapeDtypeStruct((), jnp.int32),
            )
            lowered = jax.jit(
                step_fn,
                in_shardings=(state_shard, in_batch_shard),
                out_shardings=(state_shard, None),
                donate_argnums=(0,),
            ).lower(abstract_state, specs)
        elif cell.step == "prefill":
            def serve_prefill(params, batch):
                return model.prefill(params, batch, max_len=cell.seq_len)

            pshard = sharding_for(mesh, model.param_spec())
            lowered = jax.jit(
                serve_prefill, in_shardings=(pshard, in_batch_shard)
            ).lower(model.abstract_params(), specs)
        else:  # decode
            def serve_decode(params, caches, batch):
                tok = batch.get("tokens", batch.get("frames"))
                return model.decode_step(params, caches, tok)

            pshard = sharding_for(mesh, model.param_spec())
            cshard = sharding_for(
                mesh,
                model.cache_spec(shard_seq=decode_shard_seq, shard_batch=shard_batch),
            )
            abstract_caches = jax.eval_shape(
                lambda: model.init_caches(cell.global_batch, cell.seq_len)
            )
            lowered = jax.jit(
                serve_decode,
                in_shardings=(pshard, cshard, in_batch_shard),
                out_shardings=(None, cshard),
                donate_argnums=(1,),
            ).lower(model.abstract_params(), abstract_caches, specs)

        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
        "output_bytes": getattr(ma, "output_size_in_bytes", None),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", None),
    }
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis"] = {
        "flops": float(ca.get("flops", 0.0)),
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
    }
    t2 = time.perf_counter()
    hlo = analyze_hlo(compiled.as_text(), num_partitions=n_dev)
    rec["analyze_s"] = round(time.perf_counter() - t2, 2)
    rec["hlo"] = hlo.as_dict()
    tokens = cell.global_batch * (cell.seq_len if cell.step != "decode" else 1)
    mf = model_flops_estimate(cfg, cell)
    rec["model_flops"] = mf
    rec["roofline"] = roofline_terms(hlo, n_devices=n_dev, model_flops=mf["total"])
    rec["tokens"] = tokens
    rec["ok"] = True
    return rec


def run_mwu_cell(mesh_kind: str, scale: int = 22, edgefactor: int = 16):
    """Dry-run the paper's own workload: distributed MWU matching on a
    synthetic 2^scale-vertex graph, 2-D partitioned over the production
    mesh; multi-pod runs pod-parallel bound search (DESIGN.md §5)."""
    from ..core.mwu_dist import make_pod_parallel_solver, _dist_solve_local
    from ..core.mwu import make_eta

    mesh = make_production_mesh(multi_pod=(mesh_kind == "pod2"))
    n_dev = mesh.devices.size
    G = 16
    n = 1 << scale
    m = edgefactor * n
    block = (n + G - 1) // G
    e_cell = int(m / (G * G) * 1.3)
    rec = {"arch": "mwu-graph", "shape": f"match-2^{scale}", "mesh": mesh_kind,
           "step": "mwu", "ok": False}

    u = jax.ShapeDtypeStruct((G, G, e_cell), jnp.int32)
    v = jax.ShapeDtypeStruct((G, G, e_cell), jnp.int32)
    msk = jax.ShapeDtypeStruct((G, G, e_cell), jnp.bool_)

    t0 = time.perf_counter()
    with mesh:
        if mesh_kind == "pod2":
            fn = make_pod_parallel_solver(mesh, G, block, n, m, ls_cap=9)
            bounds = jax.ShapeDtypeStruct((2,), jnp.float32)
            shardings = (
                NamedSharding(mesh, P("pod")),
                NamedSharding(mesh, P("data", "model", None)),
                NamedSharding(mesh, P("data", "model", None)),
                NamedSharding(mesh, P("data", "model", None)),
            )
            lowered = jax.jit(fn, in_shardings=shardings).lower(bounds, u, v, msk)
        else:
            eta = jnp.asarray(make_eta(n + 1, 0.1), jnp.float32)

            def single(u, v, msk, x0):
                def inner(u, v, msk, x0):
                    out = _dist_solve_local(
                        G, block, n, eta, 0.1, jnp.float32(1.0 / (n / 4)), 5000,
                        u[0, 0], v[0, 0], msk[0, 0], x0[0, 0], ls_cap=9,
                    )
                    x, *rest = out
                    return (x[None, None], *rest)

                return jax.shard_map(
                    inner, mesh=mesh,
                    in_specs=(P("data", "model", None),) * 4,
                    out_specs=(P("data", "model", None), P(), P(), P(), P(), P()),
                    check_vma=False,
                )(u, v, msk, x0)

            x0 = jax.ShapeDtypeStruct((G, G, e_cell), jnp.float32)
            shardings = (NamedSharding(mesh, P("data", "model", None)),) * 4
            lowered = jax.jit(single, in_shardings=shardings).lower(u, v, msk, x0)
        rec["lower_s"] = round(time.perf_counter() - t0, 2)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.perf_counter() - t1, 2)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
    }
    hlo = analyze_hlo(compiled.as_text(), num_partitions=n_dev)
    rec["hlo"] = hlo.as_dict()
    # per-iteration model cost: 2 SpMVs (4 nnz flops each) + O(nnz) vector
    rec["model_flops"] = {"total": 5000 * 12.0 * 2 * m}
    rec["roofline"] = roofline_terms(hlo, n_devices=n_dev, model_flops=rec["model_flops"]["total"])
    rec["ok"] = True
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "pod2", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--attn-chunk", type=int, default=None)
    ap.add_argument("--no-shard-seq", action="store_true",
                    help="decode: replicate the KV cache seq dim instead of TP-sharding")
    ap.add_argument("--remat", default=None, choices=[None, "none", "full"])
    args = ap.parse_args()

    cells = []
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = ["single", "pod2"] if args.mesh == "both" else [args.mesh]
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))

    if args.list:
        for a, s, m in cells:
            r = skip_reason(get(a), SHAPES[s])
            print(f"{a:20s} {s:12s} {m:7s} {'SKIP: '+r if r else 'run'}")
        return

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    if args.arch == "mwu-graph":
        for m in meshes:
            out = OUT_DIR / f"mwu-graph__match__{m}.json"
            print(f"=== mwu-graph / match / {m} ===", flush=True)
            try:
                rec = run_mwu_cell(m)
            except Exception as e:
                rec = {"arch": "mwu-graph", "mesh": m, "ok": False,
                       "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
                print(f"  FAILED: {rec['error'][:300]}", flush=True)
            out.write_text(json.dumps(rec, indent=1))
            if rec.get("ok"):
                r = rec["roofline"]
                print(f"  ok compile={rec['compile_s']}s compute={r['compute_s']:.3e}s "
                      f"memory={r['memory_s']:.3e}s collective={r['collective_s']:.3e}s "
                      f"bottleneck={r['bottleneck']}", flush=True)
        return
    for a, s, m in cells:
        tag = f"__{args.tag}" if args.tag else ""
        out = OUT_DIR / f"{a}__{s}__{m}{tag}.json"
        print(f"=== {a} / {s} / {m} ===", flush=True)
        try:
            rec = run_cell(a, s, m, loss_chunk=args.loss_chunk,
                           attn_chunk=args.attn_chunk, extra_tag=args.tag,
                           decode_shard_seq=not args.no_shard_seq,
                           remat=args.remat)
        except Exception as e:  # record failures: they are dry-run bugs
            rec = {
                "arch": a, "shape": s, "mesh": m, "ok": False,
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
            print(f"  FAILED: {rec['error'][:300]}", flush=True)
        out.write_text(json.dumps(rec, indent=1))
        if rec.get("ok"):
            r = rec["roofline"]
            print(
                f"  ok lower={rec['lower_s']}s compile={rec['compile_s']}s "
                f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                f"collective={r['collective_s']:.3e}s bottleneck={r['bottleneck']}",
                flush=True,
            )
        elif rec.get("skipped"):
            print(f"  skipped: {rec['reason']}", flush=True)


if __name__ == "__main__":
    main()
