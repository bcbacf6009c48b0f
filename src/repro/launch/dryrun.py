import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST precede any other import (jax locks the device count
on first init); 512 placeholder host devices let ``jax.make_mesh`` build the
production meshes.  Nothing here allocates full-size arrays — inputs and
params are ShapeDtypeStructs throughout.

Per cell this driver:
  1. builds the jitted step (train_step / prefill / serve_step per the
     shape's kind) with the sharding rules of repro.train.sharding,
  2. ``.lower(...)`` + ``.compile()`` — a failure here (sharding mismatch,
     OOM at compile, unsupported collective) is a bug in the system,
  3. prints ``memory_analysis()`` / ``cost_analysis()`` and extracts the
     three roofline terms (repro.launch.roofline) from the compiled HLO,
  4. appends the record to the output JSON (incremental — resumable).

Usage:
  python -m repro.launch.dryrun --arch gemma3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun.json
  python -m repro.launch.dryrun --all --mesh single --weights dense   # baseline
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import repro.configs as configs
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import model_zoo as zoo
from repro.optim import OptConfig
from repro.optim.optimizer import AdamWState
from repro.serving.engine import freeze_params
from repro.train import TrainState, init_state, make_train_step, sharding

BIG_PARAMS = 60e9  # above this, bf16 adam moments (fits 400B on one pod)


def cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()``: one flat dict (empty when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}


def _named(mesh, specs):
    return sharding.to_named(mesh, specs)


def lower_cell(cfg, shape, mesh, *, weights: str = "packed", fsdp: bool = True,
               remat: bool = True, cache_dtype=jnp.bfloat16):
    """Build and lower the cell's step function.  Returns (lowered, meta)."""
    kind = shape.kind
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)

    if kind == "train":
        opt_cfg = OptConfig(
            moment_dtype="bfloat16" if cfg.n_params() > BIG_PARAMS else "float32")
        state_sds = jax.eval_shape(lambda k: init_state(cfg, k, opt_cfg), key_sds)
        pspecs = sharding.param_specs(state_sds.params, mesh, fsdp=fsdp)
        mspecs = sharding.param_specs(state_sds.opt.mu, mesh, fsdp=fsdp)
        state_specs = TrainState(params=pspecs,
                                 opt=AdamWState(mu=mspecs, nu=mspecs, count=P()),
                                 step=P(), err_buf=None)
        in_specs = zoo.input_specs(cfg, shape)
        batch_sds = {k: v for k, v in in_specs.items()}
        bspecs = sharding.batch_specs(mesh, batch_sds)
        step = make_train_step(cfg, opt_cfg, remat=remat)
        fn = jax.jit(step,
                     in_shardings=(_named(mesh, state_specs), _named(mesh, bspecs)),
                     out_shardings=(_named(mesh, state_specs), None),
                     donate_argnums=(0,))
        lowered = fn.lower(state_sds, batch_sds)
        return lowered, {"mode": "train_step"}

    # Inference cells: params in the requested weight format.
    # NOTE serve cells default to fsdp=False: packed 2-bit weights fit the TP
    # shards outright (qwen3-32B packed = 0.5 GB/shard), and FSDP would trade
    # that residency for per-layer weight all-gathers every decode step —
    # measured +2.7 s/step collective term on qwen3 decode_32k (§Perf iter 1).
    params_sds = jax.eval_shape(lambda k: zoo.init_params(cfg, k), key_sds)
    if weights == "packed":
        params_sds = jax.eval_shape(freeze_params, params_sds)
    elif weights == "dense":
        # fp16-kernel baseline: ternary values materialized in bf16.
        params_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
            if x.dtype == jnp.float32 and x.ndim >= 2 else x, params_sds)
    bytes_per_w = 0.25 if weights == "packed" else 2.0
    shard_gb = cfg.n_params() * bytes_per_w / mesh.shape["model"] / 1e9
    serve_fsdp = shard_gb > 8.0  # only when TP shards alone would not fit
    pspecs = sharding.param_specs(params_sds, mesh, fsdp=serve_fsdp)
    pnamed = _named(mesh, pspecs)

    in_specs = zoo.input_specs(cfg, shape, cache_dtype=cache_dtype)
    cache_sds = in_specs.pop("cache")
    cspecs = sharding.cache_specs(mesh, cache_sds, cfg.n_kv_heads)
    cnamed = _named(mesh, cspecs)

    if kind == "prefill":
        batch_sds = in_specs
        bspecs = sharding.batch_specs(mesh, batch_sds)
        fn = jax.jit(
            lambda p, b, c: zoo.prefill(cfg, p, b, c, train=False),
            in_shardings=(pnamed, _named(mesh, bspecs), cnamed),
            out_shardings=(None, cnamed),
            donate_argnums=(2,))
        lowered = fn.lower(params_sds, batch_sds, cache_sds)
        return lowered, {"mode": "prefill"}

    # decode / serve_step
    tok_sds = in_specs["tokens"]
    tspec = sharding.batch_specs(mesh, {"tokens": tok_sds})["tokens"]
    fn = jax.jit(
        lambda p, tk, c, t: zoo.decode_step(cfg, p, tk, c, t, train=False),
        in_shardings=(pnamed, _named(mesh, {"tokens": tspec})["tokens"], cnamed,
                      None),
        out_shardings=(None, cnamed),
        donate_argnums=(2,))
    lowered = fn.lower(params_sds, tok_sds, cache_sds, in_specs["t"])
    return lowered, {"mode": "serve_step"}


def run_cell(cfg, shape, mesh, mesh_name: str, weights: str = "packed",
             verbose: bool = True, **kw) -> dict:
    from repro.utils import act_sharding

    act_sharding.set_mesh(mesh)  # pin activation layouts to this mesh
    chips = mesh.devices.size
    rec = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
           "chips": int(chips), "weights": weights, "status": "ok"}
    t0 = time.time()
    try:
        lowered, meta = lower_cell(cfg, shape, mesh, weights=weights, **kw)
        rec.update(meta)
        compiled = lowered.compile()
        cost = cost_dict(compiled)
        try:
            mem = compiled.memory_analysis()
        except Exception:
            mem = None
        hlo = compiled.as_text()
        mf = rl.model_flops_for_cell(cfg, shape)
        roof = rl.analyze(cfg.name, shape.name, mesh_name, int(chips),
                          cost or {}, hlo, mf, memory_stats=mem)
        rec["roofline"] = roof.to_json()
        rec["cost"] = {k: float(v) for k, v in (cost or {}).items()
                       if isinstance(v, (int, float))}
        if mem is not None:
            rec["memory_analysis"] = {
                a: float(getattr(mem, a))
                for a in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "generated_code_size_in_bytes")
                if hasattr(mem, a)}
            if verbose:
                print(f"  memory_analysis: {rec['memory_analysis']}")
        if verbose:
            r = rec["roofline"]
            print(f"  flops/dev={r['flops_per_device']:.3e} "
                  f"bytes/dev={r['bytes_per_device']:.3e} "
                  f"coll/dev={r['collective_bytes_per_device']:.3e} -> "
                  f"bound={r['bound']}")
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"  FAILED: {rec['error']}")
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--weights", choices=["packed", "dense", "latent"], default="packed")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--cache-dtype", default="bfloat16")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-done", action="store_true",
                    help="skip cells already present in --out")
    args = ap.parse_args()

    if args.all:
        cells = [(cfg, shape) for cfg, shape, _ in configs.cells()]
    else:
        cfg = configs.get(args.arch)
        shapes = [configs.SHAPES[args.shape]] if args.shape else [
            s for _, s, skip in configs.cells() if _.name == cfg.name and not skip]
        cells = [(cfg, s) for s in shapes]

    mesh_list = []
    if args.mesh in ("single", "both"):
        mesh_list.append(("single", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        mesh_list.append(("multi", make_production_mesh(multi_pod=True)))

    done = set()
    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
        if args.skip_done:
            done = {(r["arch"], r["shape"], r["mesh"], r.get("weights", "packed"))
                    for r in results if r.get("status") == "ok"}

    for cfg, shape in cells:
        for mesh_name, mesh in mesh_list:
            keyid = (cfg.name, shape.name, mesh_name, args.weights)
            if keyid in done:
                continue
            print(f"[dryrun] {cfg.name} x {shape.name} x {mesh_name} "
                  f"({args.weights})")
            rec = run_cell(cfg, shape, mesh, mesh_name, weights=args.weights,
                           fsdp=not args.no_fsdp, remat=not args.no_remat,
                           cache_dtype=jnp.dtype(args.cache_dtype))
            results.append(rec)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
            print(f"  -> {rec['status']} ({rec['wall_s']}s)")

    n_ok = sum(1 for r in results if r["status"] == "ok")
    print(f"\n{n_ok}/{len(results)} cells OK")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
