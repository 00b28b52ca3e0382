"""Shared benchmark machinery: corpus builders, service-time measurement,
and the two-lane queueing simulator used to replay the paper's QPS grids.

Methodology (EXPERIMENTS.md §Paper-repro): the container is CPU-only, so
absolute GPU milliseconds are not reproducible — but the paper's effects are
*structural* (realloc cost grows with list length; serial execution blocks
search behind insert; block insertion is O(1)).  We measure real service
times per system on CPU, then replay Poisson arrival traces through a
deterministic queue model:

* serial systems (Faiss/RAFT/Rt-cpu, Fig. 2a): ONE lane; every request
  (search batch or insert batch) occupies the lane for its measured service
  time; latency = completion - arrival.
* RTAMS (Fig. 2b): search lane(s) and a dedicated insert lane run
  concurrently (the multi-stream architecture); search batches <= 10, insert
  batches per the paper's dynamic batching.

The threaded ServingRuntime (core/scheduler.py) is validated separately in
tests; the queue model makes the full 1000-10000 QPS grid tractable and
deterministic.
"""

from __future__ import annotations

import dataclasses
import platform
import time
from typing import Callable, Optional

import numpy as np
import jax

from repro.core import build_ivf
from repro.core.baselines import FaissLikeIndex, RaftLikeIndex, RtCpuIndex
from repro.data.synthetic import dssm_like, sift_like

#: Version of the shared BENCH_*.json provenance block.  Bump when the
#: block's key set changes shape; readers (docs/observability.md tooling,
#: cross-run diffing) key their expectations off it.
BENCH_SCHEMA_VERSION = 1


def provenance(benchmark: str, *, fast: Optional[bool] = None,
               geometry: Optional[dict] = None,
               samples: Optional[dict] = None,
               extra: Optional[dict] = None) -> dict:
    """Uniform ``provenance`` block stamped into every ``BENCH_*.json``.

    Before this helper each benchmark invented its own partial ``meta``;
    two BENCH files from different runs could not be compared because
    neither said what geometry or sample counts produced it.  Keys:

    * ``schema_version`` — :data:`BENCH_SCHEMA_VERSION`;
    * ``benchmark`` — the writing script's name;
    * ``written_unix_s`` / ``python`` / ``jax`` / ``backend`` — when and
      on what stack the numbers were measured;
    * ``fast`` — CI-shrunk grid or the full one (when the script has one);
    * ``geometry`` — corpus/config shape (dim, n, clusters, ...);
    * ``samples`` — how many measurements back each reported number.
    """
    out = {
        "schema_version": BENCH_SCHEMA_VERSION,
        "benchmark": benchmark,
        "written_unix_s": round(time.time(), 3),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "backend": jax.default_backend(),
    }
    if fast is not None:
        out["fast"] = bool(fast)
    if geometry:
        out["geometry"] = dict(geometry)
    if samples:
        out["samples"] = dict(samples)
    if extra:
        out.update(extra)
    return out


def timed(fn, *args, warmup=1, iters=5) -> float:
    """Median wall-clock seconds per call, each call blocked on until its
    result is ready.  Errors (device ones included) raise."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def build_systems(corpus: np.ndarray, n_clusters: int, *, block_size=64,
                  nprobe=8, k=10, max_chain=512):
    """All four §4 systems over the same corpus + coarse quantizer seed."""
    n, dim = corpus.shape
    rtams = build_ivf(
        corpus, n_clusters=n_clusters, block_size=block_size,
        max_chain=max_chain, capacity_vectors=4 * n, nprobe=nprobe, k=k,
        add_batch=8192,
    )
    faiss = FaissLikeIndex(n_clusters, dim, nprobe=nprobe, k=k)
    faiss.train(corpus)
    faiss.add(corpus)
    raft = RaftLikeIndex(n_clusters, dim, nprobe=nprobe, k=k)
    raft.train(corpus)
    raft.add(corpus)
    rtcpu = RtCpuIndex(n_clusters, dim, block_size=block_size,
                       pool_blocks=4 * n // block_size + n_clusters + 16,
                       nprobe=nprobe, k=k)
    rtcpu.train(corpus)
    rtcpu.add(corpus)
    return {"rtams": rtams, "faiss_like": faiss, "raft_like": raft,
            "rt_cpu": rtcpu}


def measure_services(systems: dict, corpus: np.ndarray, *,
                     search_batch=10, insert_batch=128) -> dict:
    """Median service seconds for (search batch, insert batch) per system."""
    rng = np.random.default_rng(0)
    q = corpus[rng.integers(0, len(corpus), search_batch)]
    newv = corpus[rng.integers(0, len(corpus), insert_batch)] + 0.01
    out = {}
    for name, idx in systems.items():
        s = timed(lambda: idx.search(q), iters=7)
        i = timed(lambda: idx.add(newv.copy()), iters=3)
        out[name] = {"search_s": s, "insert_s": i}
    return out


@dataclasses.dataclass
class SimResult:
    search_mean_ms: float
    insert_mean_ms: float
    timeout_frac: float

    @property
    def latency_avg_ms(self) -> float:  # paper Eq. 4
        return self.search_mean_ms + self.insert_mean_ms


def simulate(
    qps_search: float,
    qps_insert: float,
    search_service_s: float,
    insert_service_s: float,
    *,
    parallel: bool,
    duration_s: float = 10.0,  # paper: first 10 seconds
    search_batch: int = 10,
    insert_batch: int = 128,
    timeout_ms: float = 20.0,  # paper: latency_avg > 20ms counted timeout
    seed: int = 0,
) -> SimResult:
    """Replay Poisson traffic through the one-lane / two-lane queue model."""
    rng = np.random.default_rng(seed)

    def poisson_times(rate, unit):
        if rate <= 0:
            return np.zeros((0,))
        n = rng.poisson(rate * duration_s / unit)
        return np.sort(rng.uniform(0, duration_s, n))

    s_arr = poisson_times(qps_search, 1)  # one query per request
    i_arr = poisson_times(qps_insert, insert_batch)  # batched vectors

    if parallel:
        lanes = {"s": 0.0, "i": 0.0}
    else:
        lanes = {"s": 0.0}

    # merge event streams in arrival order; searches batch up to
    # search_batch when the lane is busy (they queue and coalesce)
    s_lat, i_lat, timeouts, total = [], [], 0, 0
    si, ii = 0, 0
    pend_s: list[float] = []
    while si < len(s_arr) or ii < len(i_arr) or pend_s:
        next_s = s_arr[si] if si < len(s_arr) else np.inf
        next_i = i_arr[ii] if ii < len(i_arr) else np.inf
        lane_s = "s"
        lane_i = "i" if parallel else "s"
        # dispatch pending search batch as soon as the search lane frees
        if pend_s and lanes[lane_s] <= min(next_s, next_i):
            start = max(lanes[lane_s], pend_s[0])
            end = start + search_service_s
            lanes[lane_s] = end
            for a in pend_s:
                s_lat.append(end - a)
            pend_s = []
            continue
        if next_s <= next_i:
            pend_s.append(next_s)
            si += 1
            # coalesce immediately-available queued searches
            while (
                si < len(s_arr)
                and len(pend_s) < search_batch
                and s_arr[si] <= max(lanes[lane_s], pend_s[0])
            ):
                pend_s.append(s_arr[si])
                si += 1
        else:
            start = max(lanes[lane_i], next_i)
            end = start + insert_service_s
            lanes[lane_i] = end
            i_lat.append(end - next_i)
            ii += 1

    s_ms = 1e3 * float(np.mean(s_lat)) if s_lat else 0.0
    i_ms = 1e3 * float(np.mean(i_lat)) if i_lat else 0.0
    lats = np.concatenate([np.asarray(s_lat), np.asarray(i_lat)]) * 1e3
    to = float((lats > timeout_ms).mean()) if lats.size else 0.0
    return SimResult(
        search_mean_ms=min(s_ms, timeout_ms * 2),  # paper caps at timeout
        insert_mean_ms=min(i_ms, timeout_ms * 2),
        timeout_frac=to,
    )
