"""ANNS serving launcher: one of the paper's deployments, end to end.

Builds the index at its published size (``--scale 1.0`` by default),
starts the multi-stream runtime and drives open-loop Poisson search and
insert traffic through it, printing the latency statistics that
correspond to a Fig. 3 cell.  A run in which any request fails exits
non-zero.

    PYTHONPATH=src python -m repro.launch.serve --index ivfflat_sift1m \
        --qps-search 1000 --qps-insert 500 --duration 5

``build_index`` and ``serve`` are the two halves of that path; the chip
smoke test (``chip_smoke.py``) and the examples call them directly.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import os
import sys
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from repro.configs.anns import ivfflat_sift1m, ivfpq_dssm40m
from repro.core.admission import QueueFull, RequestRejected
from repro.core.block_pool import check_invariants
from repro.core.faults import FaultPlan
from repro.core.ivf import IVFIndex
from repro.core.runtime import RuntimeConfig, ServingRuntime
from repro.data.synthetic import dssm_like, sift_like

#: root of the source checkout (``src/repro/launch/serve.py`` -> ``.``)
CHECKOUT = Path(__file__).resolve().parents[3]

#: deployment name -> (config at a scale, corpus size at scale 1, generator)
INDEXES = {
    "ivfflat_sift1m": (ivfflat_sift1m, 1_000_000, sift_like),
    "ivfpq_dssm40m": (ivfpq_dssm40m, 40_000_000, dssm_like),
}
ADD_BATCH = 65536  # rows per offline-build insert dispatch (at most)
INSERT_BATCH = 16  # rows per online insert request
RESOLVE_TIMEOUT = 300.0  # seconds to wait for all of a run's requests
#: runtime counters that record an exception the runtime caught itself
RUNTIME_FAULT_COUNTERS = (
    "poisoned", "isolations", "fused_fallbacks", "worker_restarts",
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache before the first compile
    and return its directory: ``$JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads it itself), else ``<checkout>/.jax_cache`` — a fixed path,
    since the path is part of the cache key."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_index(name: str, scale: float = 1.0,
                seed: int = 0) -> tuple[IVFIndex, np.ndarray]:
    """Offline build of a deployment: the seeded corpus, k-means, then the
    corpus replayed through the online insert step in equal batches.
    Raises unless every row landed and the pool's invariants hold."""
    make_cfg, n_full, generate = INDEXES[name]
    cfg = make_cfg(scale)
    corpus = generate(int(n_full * scale), cfg.dim, seed=seed)
    index = IVFIndex(cfg)
    index.train(corpus)
    # equal batches: one compiled insert step for the whole build
    for chunk in np.array_split(corpus, -(-len(corpus) // ADD_BATCH)):
        index.add(chunk)
    dropped = int(index.state.num_dropped)
    if dropped or index.ntotal != len(corpus):
        raise RuntimeError(
            f"{name} build kept {index.ntotal} of {len(corpus)} rows "
            f"({dropped} dropped): the pool of "
            f"{index.pool_cfg.n_blocks} blocks is too small"
        )
    check_invariants(index.state, index.pool_cfg)
    return index, corpus


class ServeError(RuntimeError):
    """At least one request failed, or the runtime caught an exception."""


@dataclasses.dataclass
class ServeReport:
    searches: int  # search requests admitted
    inserts: int  # insert requests admitted
    rejected: int  # requests refused at admission (not failures)
    # every acked insert row, warm-up included: [R] ids, [R, D] vectors
    inserted_ids: np.ndarray
    inserted_vectors: np.ndarray
    stats: dict  # ServingRuntime.stats() after the traffic


def _resolve(futures: list) -> list:
    """Wait for every future (``RESOLVE_TIMEOUT`` in all); return the
    exceptions, one per failed or unresolved future."""
    _, pending = concurrent.futures.wait(futures, timeout=RESOLVE_TIMEOUT)
    errors = [TimeoutError("request unresolved after serve") for _ in pending]
    errors += [f.exception() for f in futures
               if f not in pending and f.exception() is not None]
    return errors


def _raise_on(errors: list, what: str, n: int) -> None:
    if errors:
        raise ServeError(
            f"{len(errors)} of {n} {what} failed; first: {errors[0]!r}"
        ) from errors[0]


def serve(index: IVFIndex, corpus: np.ndarray, runtime_cfg: RuntimeConfig,
          qps_search: float, qps_insert: float, duration: float,
          seed: int = 0, *,
          faults: Optional[FaultPlan] = None) -> ServeReport:
    """Serve open-loop Poisson traffic for ``duration`` seconds: searches of
    one corpus row each at ``qps_search``, and inserts of perturbed corpus
    rows at ``qps_insert`` rows/s in requests of ``INSERT_BATCH`` rows.

    One warm-up search and insert compile the steps first; the latency
    statistics are reset after them.  Every future is resolved before
    returning.  Raises ``ServeError`` if any request failed or the runtime
    caught an exception (``RUNTIME_FAULT_COUNTERS``); refusals at
    admission are counted in the report, not raised."""
    rng = np.random.default_rng(seed)
    rt = ServingRuntime(index, runtime_cfg, faults)
    try:
        warm_rows = corpus[:INSERT_BATCH] + 0.01
        warm = [rt.submit_search(corpus[:1]), rt.submit_insert(warm_rows)]
        _raise_on(_resolve(warm), "warm-up requests", len(warm))
        rt.reset_stats()
        searches, inserts, rejected = [], [], 0
        acked = [(warm[1], warm_rows)]
        t0 = time.perf_counter()
        t_end = t0 + duration
        next_s = t0 + rng.exponential(1.0 / qps_search)
        next_i = t0 + rng.exponential(INSERT_BATCH / qps_insert)
        while (now := time.perf_counter()) < t_end:
            # open loop: every arrival that fell due is sent, however late
            while next_s <= now:
                try:
                    searches.append(rt.submit_search(
                        corpus[rng.integers(0, len(corpus), 1)]
                    ))
                except RequestRejected:
                    rejected += 1
                next_s += rng.exponential(1.0 / qps_search)
            while next_i <= now:
                pick = rng.integers(0, len(corpus), INSERT_BATCH)
                rows = corpus[pick] + 0.01
                try:
                    inserts.append((rt.submit_insert(rows), rows))
                except QueueFull:
                    rejected += 1
                next_i += rng.exponential(INSERT_BATCH / qps_insert)
            time.sleep(max(0.0, min(next_s, next_i, t_end) - now))
        futures = searches + [f for f, _ in inserts]
        _raise_on(_resolve(futures), "requests", len(futures))
        stats = rt.stats()
    finally:
        rt.stop()
    acked += inserts
    caught = {c: stats[c] for c in RUNTIME_FAULT_COUNTERS if stats[c]}
    if caught:
        raise ServeError(f"runtime caught exceptions while serving: {caught}")
    return ServeReport(
        searches=len(searches),
        inserts=len(inserts),
        rejected=rejected,
        inserted_ids=np.concatenate([f.result() for f, _ in acked]),
        inserted_vectors=np.concatenate([v for _, v in acked]),
        stats=stats,
    )


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--index", default="ivfflat_sift1m", choices=INDEXES)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--mode", default="parallel",
                    choices=["serial", "parallel", "fused"])
    ap.add_argument("--qps-search", type=float, default=1000)
    ap.add_argument("--qps-insert", type=float, default=500,
                    help="inserted rows per second")
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    print(f"[serve] compile cache {use_compile_cache()}")
    t0 = time.perf_counter()
    index, corpus = build_index(args.index, args.scale, args.seed)
    cfg = index.cfg
    print(f"[serve] built {args.index} at scale {args.scale} in "
          f"{time.perf_counter() - t0:.1f}s: {index.ntotal} vectors, "
          f"{cfg.n_clusters} lists, T_m={cfg.block_size}, "
          f"{index.pool_cfg.n_blocks} blocks")
    runtime_cfg = RuntimeConfig(mode=args.mode, nprobe=cfg.nprobe, k=cfg.k,
                                search_path=cfg.search_path)
    try:
        rep = serve(index, corpus, runtime_cfg, args.qps_search,
                    args.qps_insert, args.duration, args.seed)
    except ServeError as e:
        print(f"[serve] FAILED: {e}", file=sys.stderr)
        return 1
    s = rep.stats
    print(f"[serve] mode={args.mode} on {jax.devices()[0].device_kind}")
    print(f"  search {s['search'].row()}")
    print(f"  insert {s['insert'].row()}")
    print(f"  searches={rep.searches} inserts={rep.inserts} "
          f"rejected={rep.rejected} corpus={index.ntotal}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
