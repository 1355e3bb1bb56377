"""Compile each cell's step programs at its real sizes for a described
TPU v5e, with no chip: the serving engine's one-lane prefill chunk and
its full-batch decode step, and the seeded weight generator.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [cell ...]

Shapes come from ``jax.eval_shape``; nothing is allocated. Prints one
JSON line per program: the compiler's ``memory_analysis()`` (argument,
output and temporary bytes) and whether the compiled program holds a
Mosaic kernel (``tpu_custom_call``). The KV pool sizes in the
configuration files are set from the largest temporaries printed here.
"""
from __future__ import annotations

import json
import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def _on(sharding, tree):
    import jax
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def rehearse(cell, one) -> list:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench import spec, traffic
    from repro.core.quantize import KVCacheQuant
    from repro.kernels import ops
    from repro.models import api
    from repro.serving.engine import Engine

    # the kernels pick interpret mode from the default backend, which is
    # the CPU here; the described chip compiles them for Mosaic
    ops._default_interpret = lambda: False
    cfg, mix = cell.cfg, cell.mix
    fam = spec.load_module("families", cfg["family"])
    sv = cfg["serving"]
    w = jax.eval_shape(lambda: fam._stack_fn(fam._cfg_key(cfg))(
        np.uint32(0), np.uint32(0)))
    params, arch, qm = fam.to_system(cfg, _on(one, w))
    n_pages = 1 + sv["kv_pool_bytes"] // (sv["page_size"]
                                          * fam.kv_bytes_per_token(cfg))
    eng = Engine(params, arch, qm, batch_size=mix["batch"],
                 max_len=traffic.max_len(mix), scheduler="continuous",
                 kv_cache=sv["kv_cache"], kv_layout="paged",
                 page_size=sv["page_size"], n_pages=n_pages)
    cache = _on(one, jax.eval_shape(lambda: api.init_cache_paged(
        arch, n_pages, sv["page_size"], jnp.float32,
        KVCacheQuant(sv["kv_cache"]))))
    B, C, maxp = mix["batch"], sv["attn_chunk"], eng.pages_per_slot

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

    progs = {
        "weights": (fam._stack_fn(fam._cfg_key(cfg)),
                    (jax.ShapeDtypeStruct((), jnp.uint32, sharding=one),) * 2),
        "decode_paged": (eng._decode_paged,
                         (params, cache, i32(B), i32(B), i32(B, maxp),
                          i32())),
        "prefill_chunk_paged": (eng._prefill_chunk_paged,
                                (params, cache, i32(1, C), i32(1, maxp),
                                 i32(), i32())),
    }
    out = []
    for name, (fn, args) in progs.items():
        c = fn.lower(*args).compile()
        m = c.memory_analysis()
        out.append({"cell": cell.name, "program": name,
                    "argument_bytes": m.argument_size_in_bytes,
                    "output_bytes": m.output_size_in_bytes,
                    "temp_bytes": m.temp_size_in_bytes,
                    "alias_bytes": m.alias_size_in_bytes,
                    "mosaic": "tpu_custom_call" in c.as_text(),
                    "n_pages": n_pages})
    return out


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import spec
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    names = argv if argv else [w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]]
    for name in names:
        for row in rehearse(spec.load_cell(ROOT, name), one):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
