"""End-to-end serving driver (the paper's deployment, §3.3): the
multi-stream runtime under mixed search+insert traffic with batched
requests, comparing serial vs parallel vs fused execution modes.

    PYTHONPATH=src python examples/online_serving.py
"""

import time

import numpy as np

from repro.core import build_ivf
from repro.core.runtime import RuntimeConfig, ServingRuntime
from repro.data.synthetic import sift_like
from repro.launch.serve import serve


def main():
    corpus = sift_like(10_000, dim=128, seed=0)
    for mode in ("serial", "parallel", "fused"):
        index = build_ivf(
            corpus, n_clusters=32, block_size=64, max_chain=64,
            capacity_vectors=40_000, nprobe=8, k=10,
        )
        # fault-tolerant serving posture (docs/serving_ops.md): bound the
        # mutation backlog, expire requests instead of serving them
        # arbitrarily late, and degrade before falling over
        cfg = RuntimeConfig(mode=mode, nprobe=8, k=10, flush_min=16,
                            flush_interval=0.1,
                            max_pending_mutations=4096,
                            default_deadline=5.0,
                            degradation_ladder=("no_rerank", "half_nprobe"))
        # open-loop Poisson search + insert traffic; raises if any request
        # fails (repro.launch.serve)
        rep = serve(index, corpus, cfg, qps_search=3, qps_insert=20,
                    duration=4.0)
        s = rep.stats
        print(f"mode={mode:<9} search {s['search'].row()}")
        print(f"{'':15}insert {s['insert'].row()}  rejected={rep.rejected}")
        rt = ServingRuntime(index, cfg)
        try:
            # the mutation stream rides the same lane: deletes tombstone
            # through the device id map, updates replace in place under
            # the same id (one fused dispatch each); auto_compact reclaims
            # the dead space once a cluster crosses the trigger
            rng = np.random.default_rng(7)
            victims = rng.choice(5000, 400, replace=False).astype(np.int32)
            rt.submit_delete(victims).result(timeout=30)
            keep = np.asarray([6000, 6001, 6002], np.int32)
            rt.submit_update(corpus[keep] * 0.5, keep).result(timeout=30)
            time.sleep(0.2)
            s = rt.stats()
            print(f"{'':15}mutation {s['mutation'].row()}")
            print(f"{'':15}deletes={s['deletes']} updates={s['updates']} "
                  f"live={s['live_vectors']} "
                  f"dead_frac={s['dead_fraction']:.3f} "
                  f"util={s['utilisation']:.3f}")
            print(f"{'':15}shed={s['shed_search']}/{s['shed_mutation']} "
                  f"rejected={s['rejected_search']}/"
                  f"{s['rejected_mutation']} "
                  f"rung={s['degradation_rung']}")
            print(f"{'':15}corpus now {rt.index.ntotal} live vectors")
        finally:
            rt.stop()


if __name__ == "__main__":
    main()
