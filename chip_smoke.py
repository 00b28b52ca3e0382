"""Chip smoke test: the paper's SIFT1M deployment, at full size, on one TPU.

    python chip_smoke.py

Builds ``ivfflat_sift1m`` (1M x 128 f32, 4,000 lists) through
``repro.launch.serve.build_index``, serves it with ``serve`` in the
``parallel`` and ``fused`` modes at the lowest Fig. 3 cell (1,000 search
QPS, 500 inserted rows/s), sends a delete and an update batch, and checks
the answers against plain references (``repro.core.reference``):

* mean overlap@10 with the IVF-exact f32-HIGHEST reference >= 0.99;
* every sampled acked insert finds itself, no deleted id is returned, an
  updated id is found at its new vector and not at its old one;
* no row dropped, every acked row resident, and the pool's invariants.

Latencies and recall against the whole live set are printed as
information: this is not a benchmark.  The script runs on a TPU only: it
exits non-zero, printing no result, when JAX finds none.  Any failed check
raises.  The last line of stdout is one JSON object naming the device.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OVERLAP_GATE = 0.99
N_OVERLAP_QUERIES = 256
N_INSERT_SAMPLE = 64
N_DELETE = 32
N_UPDATE = 32
#: JAX's monitoring event for one backend compile (cache reads included)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def search(rt, queries, timeout: float = 300.0):
    """Serve each row as its own request, no more in flight than the
    runtime has slots; returns ([Q, k] dists, [Q, k] ids)."""
    import numpy as np

    ds, ids = [], []
    wave = rt.cfg.n_slots
    for s in range(0, len(queries), wave):
        futs = [rt.submit_search(q[None]) for q in queries[s : s + wave]]
        for f in futs:
            d, i = f.result(timeout=timeout)
            ds.append(d[0])
            ids.append(i[0])
    return np.stack(ds), np.stack(ids)


def overlap_at_k(got, want) -> float:
    """Mean over rows of |got ∩ want| / k, ignoring -1 padding."""
    k = want.shape[1]
    return sum(
        len((set(g) & set(w)) - {-1}) / k for g, w in zip(got, want)
    ) / len(want)


def run(scale: float = 1.0, seed: int = 0, duration: float = 5.0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.block_pool import check_invariants
    from repro.core.reference import ivf_exact_topk, live_rows
    from repro.core.runtime import RuntimeConfig, ServingRuntime
    from repro.core.search import make_search_fn
    from repro.launch.serve import build_index, serve

    dev = jax.devices()[0]

    def peak() -> str:
        m = dev.memory_stats() or {}
        if "peak_bytes_in_use" not in m:
            return "not reported"
        return f"{m['peak_bytes_in_use'] / 2**30:.2f} GiB"

    t0 = time.perf_counter()
    index, corpus = build_index("ivfflat_sift1m", scale, seed)
    n, dim = corpus.shape
    st = index.stats()
    log(f"build {time.perf_counter() - t0:.1f}s: ntotal={index.ntotal} "
        f"blocks used={st['blocks_in_use']} allocated="
        f"{index.pool_cfg.n_blocks} num_dropped={st['num_dropped']} "
        f"device peak={peak()}")
    check(index.ntotal == n and st["num_dropped"] == 0, "build dropped rows")

    rng = np.random.default_rng(seed + 1)
    deleted: set = set()
    touched: set = set()  # ids deleted or updated: their corpus row is stale
    resident = n
    icfg = index.cfg
    for mode in ("parallel", "fused"):
        cfg = RuntimeConfig(mode=mode, nprobe=icfg.nprobe, k=icfg.k,
                            search_path=icfg.search_path)
        rep = serve(index, corpus, cfg, qps_search=1000, qps_insert=500,
                    duration=duration, seed=seed)
        resident += len(rep.inserted_ids)
        s = rep.stats
        ps, pi = s["percentiles"]["search"], s["percentiles"]["insert"]
        log(f"{mode}: searches={rep.searches} inserts={rep.inserts} "
            f"rejected={rep.rejected} (information, not a benchmark: "
            f"search p50={ps['p50_ms']:.2f} p99={ps['p99_ms']:.2f} ms, "
            f"insert p50={pi['p50_ms']:.2f} p99={pi['p99_ms']:.2f} ms)")

        rt = ServingRuntime(index, cfg)

        def served(queries):
            """Served ids; no deleted id may ever come back."""
            _, ids = search(rt, queries)
            check(not deleted & set(ids.ravel().tolist()),
                  f"{mode}: a deleted id was returned")
            return ids

        try:
            # reads after writes: acked inserts find themselves
            pick = rng.choice(len(rep.inserted_ids), N_INSERT_SAMPLE,
                              replace=False)
            got = served(rep.inserted_vectors[pick])
            lost = [int(i) for i, row in zip(rep.inserted_ids[pick], got)
                    if i not in row]
            check(not lost, f"{mode}: acked inserts not found: {lost}")

            # one delete batch: never returned again
            fresh = np.setdiff1d(rep.inserted_ids, np.fromiter(touched, int))
            victims = np.concatenate([
                rng.choice(fresh, N_DELETE // 2, replace=False),
                rng.choice(np.setdiff1d(np.arange(n),
                                        np.fromiter(touched, int)),
                           N_DELETE // 2, replace=False),
            ]).astype(np.int32)
            by_id = dict(zip(rep.inserted_ids.tolist(),
                             rep.inserted_vectors))
            victim_rows = np.stack([by_id[i] if i in by_id else corpus[i]
                                    for i in victims.tolist()])
            rt.submit_delete(victims).result(timeout=300)
            deleted |= set(victims.tolist())
            touched |= set(victims.tolist())
            resident -= len(victims)
            served(victim_rows)

            # one update batch: found at the new vector, not at the old
            targets = rng.choice(
                np.setdiff1d(np.arange(n), np.fromiter(touched, int)),
                N_UPDATE, replace=False).astype(np.int32)
            new = (corpus[rng.choice(n, N_UPDATE)]
                   + rng.normal(0, 4.0, (N_UPDATE, dim))).astype(np.float32)
            rt.submit_update(new, targets).result(timeout=300)
            touched |= set(targets.tolist())
            at_new = served(new)
            at_old = served(corpus[targets])
            check(all(t in row for t, row in zip(targets, at_new)),
                  f"{mode}: an updated id is missing at its new vector")
            check(not any(t in row for t, row in zip(targets, at_old)),
                  f"{mode}: an updated id is still found at its old vector")

            # overlap gate against the IVF-exact reference
            queries = corpus[rng.choice(n, N_OVERLAP_QUERIES, replace=False)]
            got = served(queries)
            live = live_rows(index.state)
            _, want = ivf_exact_topk(index.state, queries, nprobe=icfg.nprobe,
                                     k=icfg.k, live=live)
            _, exact = ivf_exact_topk(index.state, queries, nprobe=None,
                                      k=icfg.k, live=live)
            ov = overlap_at_k(got, want)
            log(f"{mode}: overlap@{icfg.k} with the IVF-exact f32-HIGHEST "
                f"reference = {ov:.4f} (gate {OVERLAP_GATE}); recall@"
                f"{icfg.k} vs exact search of the live set = "
                f"{overlap_at_k(got, exact):.4f} (information)")
            check(ov >= OVERLAP_GATE, f"{mode}: overlap {ov} < gate")
        finally:
            rt.stop()

        st = index.stats()
        check(st["num_dropped"] == 0, f"{mode}: rows dropped")
        check(index.ntotal == resident,
              f"{mode}: {index.ntotal} rows resident, {resident} acked")
        check_invariants(index.state, index.pool_cfg)
        log(f"{mode}: state ok: ntotal={index.ntotal} num_dropped=0 "
            f"invariants hold, device peak={peak()}")

    step = make_search_fn(index.pool_cfg, nprobe=icfg.nprobe, k=icfg.k,
                          path=icfg.search_path,
                          chain_budget=index._chain_budget())
    hlo = step.lower(
        index.state, jnp.zeros((16, dim), jnp.float32)
    ).compile().as_text()
    n_kernels = hlo.count('custom_call_target="tpu_custom_call"')
    note = (" (XLA gathers and matmuls; no Pallas kernel)"
            if n_kernels == 0 else "")
    log(f"served search step ({icfg.search_path}, Q=16) compiles to "
        f"{n_kernels} Pallas custom calls{note}")


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              "this script runs on the chip only", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.serve import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    compile_s = [0.0]

    def on_duration(event: str, secs: float, **_):
        if event == BACKEND_COMPILE_EVENT:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    t0 = time.perf_counter()
    run()
    log(f"compile cache {cache}; {compile_s[0]:.1f}s compiling of "
        f"{time.perf_counter() - t0:.1f}s total")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
