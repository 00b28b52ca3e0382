"""Compile the DSSM-PQ16 cell's programs for a described v5e, with no chip:
the largest compiles of its set-up and window, and their memory.

    JAX_PLATFORMS=cpu python bench/rehearse.py [--scale 0.25]

* k-means' assignment step over the whole corpus (``kmeans._assign``);
* the PQ ``block_table`` search step at Q = 8 and 16, budgets 2 and 4;
* the PQ insert step at B = 1,024;
* the check's reference programs over every row: the nearest-list
  assignment (``common.nearest_two``), one step of the reference k-means,
  the PQ encoding at ``highest`` and at ``fp8``, and one step of the
  reference's codebook k-means over its training sample.

Each line gives the program's argument, output and temporary bytes as the
TPU compiler reports them.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

GIB = 2**30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--scale", type=float, default=0.25)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.configs.anns import ivfpq_dssm40m
    from repro.core import pq as pqmod
    from repro.core.block_pool import init_state
    from repro.core.insert import make_insert_fn
    from repro.core.search import make_search_fn

    kmeans = importlib.import_module("repro.core.kmeans")
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = ivfpq_dssm40m(args.scale)
    pc = cfg.pool_config()
    n = int(40_000_000 * args.scale)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def report(what, lowered):
        t = time.perf_counter()
        m = lowered.compile().memory_analysis()
        print(f"{what}: compiled in {time.perf_counter() - t:.1f}s; "
              f"arguments {m.argument_size_in_bytes / GIB:.2f} GiB, "
              f"outputs {m.output_size_in_bytes / GIB:.2f} GiB, "
              f"temporaries {m.temp_size_in_bytes / GIB:.2f} GiB",
              flush=True)

    print(f"ivfpq_dssm40m({args.scale}): {n} rows, {cfg.n_clusters} lists, "
          f"{pc.n_blocks} blocks")
    report(f"kmeans._assign [{n}, {cfg.dim}] x [{cfg.n_clusters}, "
           f"{cfg.dim}]",
           kmeans._assign.lower(spec((n, cfg.dim), jnp.float32),
                                spec((cfg.n_clusters, cfg.dim), jnp.float32),
                                n_clusters=cfg.n_clusters))
    state = jax.eval_shape(lambda c: init_state(pc, c),
                           jax.ShapeDtypeStruct((cfg.n_clusters, cfg.dim),
                                                jnp.float32))
    state = jax.tree.map(lambda s: spec(s.shape, s.dtype), state)
    books = pqmod.PQParams(codebooks=jnp.zeros(
        (cfg.pq_m, pqmod.KSUB, cfg.dim // cfg.pq_m), jnp.float32))
    for budget in (2, 4):
        step = make_search_fn(pc, nprobe=cfg.nprobe, k=cfg.k,
                              path="block_table",
                              score_fn=pqmod.pq_score_fn(books),
                              chain_budget=budget, pq=books)
        for q in (8, 16):
            report(f"PQ block_table search Q={q} budget={budget}",
                   step.lower(state, spec((q, cfg.dim), jnp.float32)))
    insert = make_insert_fn(pc, encode=pqmod.make_pq_encode_fn(books))
    b = 1024
    report(f"PQ insert B={b}",
           insert.lower(state, spec((b, cfg.dim), jnp.float32),
                        spec((b,), jnp.int32), spec((b,), jnp.bool_)))

    from bench.reference import common, ivf_pq
    from bench.reference import kmeans as ref_kmeans

    rows = -(-n // (1 << 16)) * (1 << 16)  # padded as the check pads them
    chunk = common.chunk_rows(rows, cfg.n_clusters)
    x = spec((rows, cfg.dim), jnp.float32)
    c = spec((cfg.n_clusters, cfg.dim), jnp.float32)
    report(f"check: nearest_two [{rows}, {cfg.dim}] x [{cfg.n_clusters}, "
           f"{cfg.dim}], chunk {chunk}",
           common._nearest_two.lower(x, c, chunk=chunk))
    report("check: one reference Lloyd step",
           ref_kmeans._lloyd_step.lower(x, n, c, chunk=chunk))
    for precision in ("highest", "fp8"):
        report(f"check: PQ encoding at {precision}",
               ivf_pq._encode.lower(
                   x, spec((rows,), jnp.int32), c,
                   spec(books.codebooks.shape, jnp.float32),
                   precision=precision, chunk=ivf_pq.ENCODE_CHUNK))
    report("check: one reference codebook step",
           ivf_pq._codebook_step.lower(
               spec((65536, cfg.dim), jnp.float32), 65536,
               spec(books.codebooks.shape, jnp.float32),
               chunk=ivf_pq.ENCODE_CHUNK))
    return 0


if __name__ == "__main__":
    sys.exit(main())
