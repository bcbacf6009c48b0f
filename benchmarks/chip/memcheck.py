"""Compile a cell's programs for a described v5e, without a chip, and print
what each needs of the device's memory.

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/chip/memcheck.py \
        bitnet2b.chat [more cells]

For each cell: the packed init, and the flat serving step at the cell's
largest view bucket for both step widths (token budget and pure decode),
with the block pool donated as the engine donates it.  The numbers are the
compiler's, for one program at a time; a compile that passes is not a chip
run.
"""
from __future__ import annotations

import json
import math
import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import spec  # noqa: E402


def _specs(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _gb(n: float) -> float:
    return round(n / 1e9, 3)


def check(workload: str, one_chip) -> dict:
    from repro.models import model_zoo as zoo
    from repro.serving.engine import _flat_call, freeze_params

    cell = spec.resolve(workload)
    cfg = spec.arch(cell.config).model_config(cell.config)
    eng = cell.settings["engine"]
    slots, bs = eng["slots"], eng["block_size"]
    max_blocks = math.ceil(eng["max_len"] / bs)
    num_blocks = slots * max_blocks + 1
    view = 1 << (max_blocks - 1).bit_length()
    params = jax.eval_shape(
        lambda k: freeze_params(zoo.init_params(cfg, k), sparse=False),
        jax.random.PRNGKey(0))
    pools = jax.eval_shape(
        lambda: zoo.init_paged_cache(cfg, slots, num_blocks, bs))
    out = {"cell": workload, "view_blocks": view, "num_blocks": num_blocks,
           "params_gb": _gb(sum(x.size * x.dtype.itemsize
                                for x in jax.tree.leaves(params))),
           "pool_gb": _gb(sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(pools)))}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    from repro.serving import init_packed_params
    mem = jax.jit(lambda k: init_packed_params(cfg, k)).lower(
        key).compile().memory_analysis()
    out["init"] = {"out_gb": _gb(mem.output_size_in_bytes),
                   "temp_gb": _gb(mem.temp_size_in_bytes)}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)  # noqa: E731
    for width in (eng["token_budget"], slots):
        step = jax.jit(lambda p, pools, tbl, tk, sl, ps, er:
                       _flat_call(cfg, p, pools, tbl, tk, sl, ps, er),
                       donate_argnums=(1,))
        mem = step.lower(_specs(params, one_chip), _specs(pools, one_chip),
                         i32(slots, view), i32(width), i32(width),
                         i32(width), i32(slots)).compile().memory_analysis()
        out[f"step_T{width}"] = {
            "args_gb": _gb(mem.argument_size_in_bytes),
            "temp_gb": _gb(mem.temp_size_in_bytes),
            "out_gb": _gb(mem.output_size_in_bytes),
            "alias_gb": _gb(mem.alias_size_in_bytes),
            "args_plus_temp_gb": _gb(mem.argument_size_in_bytes
                                     + mem.temp_size_in_bytes)}
    return out


def main(argv: list[str]) -> int:
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for workload in argv or [w["name"]
                             for w in spec.load_benchmark()["workloads"]]:
        print(json.dumps(check(workload, one_chip)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
