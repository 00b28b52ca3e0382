"""User-facing IVFFlat / IVFPQ indexes over the block pool.

``IVFIndex`` owns the jitted step functions (insert / search / rearrange)
and the functional ``IVFState``.  The offline segment (paper §3.3) is built
by k-means + replaying batched inserts through the *same* insertion path the
online segment uses — there is deliberately no separate bulk loader, so the
offline/online split is purely operational, as deployed in the paper.
"""

from __future__ import annotations

import dataclasses
import zlib
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pq as pqmod
from repro.core.block_pool import IVFState, PoolConfig, init_state, pool_stats
from repro.core.insert import make_insert_fn
from repro.core.kmeans import kmeans
from repro.core.mutate import make_delete_fn, make_update_fn
from repro.core.rearrange import make_rearrange_fn
from repro.core.search import make_search_fn

#: Version stamp of the (field set, field semantics) of :class:`IVFState`
#: as serialized by ``state_to_host``.  Bump it whenever a field is added,
#: removed, re-typed, or its meaning changes — recovery refuses to load a
#: snapshot written under a different schema rather than misinterpreting
#: leaves (see repro.persist.snapshot / recovery).
STATE_SCHEMA_VERSION = 1


class StateSchemaError(RuntimeError):
    """A serialized IVFState does not match this build's schema."""


class StateChecksumError(RuntimeError):
    """A serialized IVFState leaf failed its per-leaf CRC32."""


def _leaf_crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes())


def state_to_host(state) -> "tuple[dict[str, np.ndarray], dict]":
    """One D2H transfer of the whole pytree -> ``{field: np.ndarray}`` plus
    a schema + per-leaf-CRC32 meta dict (JSON-serializable).

    bfloat16 leaves are stored as their uint16 bit pattern (npz cannot hold
    ml_dtypes natively); the logical dtype is recorded in the meta and
    restored exactly by ``state_from_host``.
    """
    fields = [f.name for f in dataclasses.fields(type(state))]
    host = jax.device_get(state)
    arrays: dict[str, np.ndarray] = {}
    leaves: dict[str, dict] = {}
    for name in fields:
        arr = np.asarray(getattr(host, name))
        logical = str(arr.dtype)
        if arr.dtype == jnp.bfloat16:
            arr = arr.view(np.uint16)
        arrays[name] = arr
        leaves[name] = {
            "crc32": _leaf_crc(arr),
            "dtype": logical,
            "shape": list(arr.shape),
        }
    meta = {
        "schema": STATE_SCHEMA_VERSION,
        "fields": fields,
        "leaves": leaves,
    }
    return arrays, meta


def state_from_host(
    arrays: "dict[str, np.ndarray]", meta: dict, *, verify: bool = True
) -> IVFState:
    """Inverse of ``state_to_host``: schema check, per-leaf CRC32 verify
    (``StateChecksumError`` names the bad leaf), then device upload."""
    if meta.get("schema") != STATE_SCHEMA_VERSION:
        raise StateSchemaError(
            f"snapshot schema {meta.get('schema')!r} != this build's "
            f"{STATE_SCHEMA_VERSION} — refusing to reinterpret leaves"
        )
    fields = [f.name for f in dataclasses.fields(IVFState)]
    if list(meta.get("fields", ())) != fields:
        raise StateSchemaError(
            f"snapshot fields {meta.get('fields')} != {fields}"
        )
    dev: dict[str, jax.Array] = {}
    for name in fields:
        if name not in arrays:
            raise StateSchemaError(f"snapshot is missing leaf {name!r}")
        arr = np.asarray(arrays[name])
        info = meta["leaves"][name]
        if verify and _leaf_crc(arr) != info["crc32"]:
            raise StateChecksumError(
                f"leaf {name!r} failed its CRC32 — snapshot bytes are "
                "corrupt, refusing to serve from it"
            )
        if info["dtype"] == "bfloat16":
            arr = arr.view(jnp.bfloat16)
        dev[name] = jnp.asarray(arr)
    return IVFState(**dev)


@dataclasses.dataclass
class IVFIndexConfig:
    n_clusters: int
    dim: int
    block_size: int = 1024  # paper deployment value T_m
    max_chain: int = 64
    pool_blocks: Optional[int] = None  # default: sized for capacity_vectors
    capacity_vectors: Optional[int] = None
    payload: str = "flat"  # "flat" | "pq"
    pq_m: int = 0
    dtype: str = "float32"  # flat payload dtype: float32 | bfloat16 | int8
    rerank: bool = False  # exact-fp32 re-rank epilogue (fused paths only)
    nprobe: int = 16
    k: int = 10
    rearrange_threshold: int = 10_000  # T'_m (paper Table 1 sweeps this)
    # mutation subsystem: compaction triggers when a cluster's tombstoned
    # fraction reaches this (see core.rearrange); id_capacity sizes the
    # device id -> location map (None = 2x pool slot capacity)
    dead_frac_threshold: float = 0.3
    id_capacity: Optional[int] = None
    # "block_table" | "chain_walk" | "union" | "union_pallas" |
    # "union_fused" | "union_fused_scan" (see core.search / docs/search_paths.md)
    search_path: str = "block_table"
    use_kernel: bool = False  # route scan through Pallas ops
    kmeans_iters: int = 10
    seed: int = 0

    def pool_config(self) -> PoolConfig:
        if self.pool_blocks is not None:
            n_blocks = self.pool_blocks
        else:
            cap = self.capacity_vectors or (self.n_clusters * self.block_size)
            # full blocks for the capacity, plus one partial tail block per
            # list: the pool cannot run out before `cap` rows are resident
            n_blocks = int(-(-cap // self.block_size)) + self.n_clusters + 16
        return PoolConfig(
            n_clusters=self.n_clusters,
            dim=self.dim,
            block_size=self.block_size,
            n_blocks=n_blocks,
            max_chain=self.max_chain,
            payload=self.payload,
            pq_m=self.pq_m,
            dtype=self.dtype,
            max_ids=self.id_capacity or 0,
        )


class IVFIndex:
    """IVFFlat (payload='flat') or IVFPQ (payload='pq') with online insertion."""

    def __init__(self, cfg: IVFIndexConfig):
        self.cfg = cfg
        self.pool_cfg = cfg.pool_config()
        self.pq: Optional[pqmod.PQParams] = None
        self.state: Optional[IVFState] = None
        self._insert_fn = None
        self._search_fns: dict = {}
        self._rearrange_fn = None
        self._next_id = 0

    # ---------------------------------------------------------- build ----
    def train(self, x: np.ndarray) -> None:
        """Train the coarse quantizer (+ PQ codebooks) on offline vectors."""
        cents = kmeans(
            x, self.cfg.n_clusters, n_iter=self.cfg.kmeans_iters, seed=self.cfg.seed
        )
        self.state = init_state(self.pool_cfg, jnp.asarray(cents))
        if self.cfg.payload == "pq":
            # residuals of a sample against their centroid
            xs = np.asarray(x[: min(len(x), 65536)], np.float32)
            assign = np.asarray(
                _assign_blockwise(jnp.asarray(xs), jnp.asarray(cents))
            )
            res = xs - cents[assign]
            self.pq = pqmod.train_pq(res, self.cfg.pq_m, seed=self.cfg.seed)
        self._build_fns()

    def _build_fns(self) -> None:
        """Build the jitted mutation/maintenance steps for the current
        (pool_cfg, pq) pair.  Split out of ``train`` so recovery can adopt
        a restored state without re-running k-means (``install_state``)."""
        encode = pqmod.make_pq_encode_fn(self.pq) if self.pq else None
        self._insert_fn = make_insert_fn(self.pool_cfg, encode=encode)
        self._delete_fn = make_delete_fn(self.pool_cfg)
        self._update_fn = make_update_fn(self.pool_cfg, encode=encode)
        self._rearrange_fn = make_rearrange_fn(
            self.pool_cfg, self.cfg.rearrange_threshold,
            dead_frac=self.cfg.dead_frac_threshold,
        )

    def install_state(self, state: IVFState, *, pq=None,
                      next_id: int = 0) -> None:
        """Adopt a restored ``IVFState`` (recovery entry point): the
        centroids/codebooks travel inside the snapshot, so no training
        data is needed — only the config must match the snapshot schema."""
        expect = self.pool_cfg.payload_shape()
        if tuple(state.pool_payload.shape) != expect:
            raise StateSchemaError(
                f"restored pool payload {tuple(state.pool_payload.shape)} "
                f"!= {expect} from config — wrong IVFIndexConfig for this "
                "snapshot"
            )
        self.pq = pq
        self.state = state
        self._next_id = int(next_id)
        self._search_fns = {}
        self._build_fns()

    def add(self, x: np.ndarray | jax.Array, ids=None) -> np.ndarray:
        """Insert a batch (offline load and online insertion share this)."""
        assert self.state is not None, "train() first"
        x = jnp.asarray(x, jnp.float32)
        b = x.shape[0]
        if ids is None:
            ids = np.arange(self._next_id, self._next_id + b, dtype=np.int32)
            # IVFIndex is a single-writer host object; concurrent submitters
            # allocate ids in ServingRuntime._mutation_args instead, so:
            # counter-ok: single-writer by contract (runtime path holds _state_lock)
            self._next_id += b
        self.state = self._insert_fn(self.state, x, jnp.asarray(ids, jnp.int32))
        return np.asarray(ids)

    # ------------------------------------------------------- mutations ----
    def delete(self, ids) -> int:
        """Tombstone a batch of ids; returns how many were actually found
        (misses — unknown / already-deleted / unmappable ids — accrue in
        ``state.num_missed``).  Dead space is reclaimed by the next
        compaction pass (``maybe_rearrange``)."""
        assert self.state is not None, "train() first"
        before = int(self.state.num_deleted)
        self.state = self._delete_fn(
            self.state, jnp.asarray(ids, jnp.int32)
        )
        return int(self.state.num_deleted) - before

    def update(self, x: np.ndarray | jax.Array, ids) -> np.ndarray:
        """Replace the vectors behind ``ids`` in one dispatch (tombstone +
        re-insert under the same id — no host round trip, no copy of any
        resident row).  Ids not currently resident degrade to plain inserts
        (upsert) and count toward ``num_missed``."""
        assert self.state is not None, "train() first"
        x = jnp.asarray(x, jnp.float32)
        ids = np.asarray(ids, np.int32)
        assert len(ids) == x.shape[0], (len(ids), x.shape)
        self.state = self._update_fn(self.state, x, jnp.asarray(ids))
        return ids

    def stats(self) -> dict:
        """Live-occupancy / reclamation gauges (see block_pool.pool_stats)."""
        return pool_stats(self.state, self.pool_cfg)

    # --------------------------------------------------------- search ----
    def _chain_budget(self) -> int:
        """Adaptive static scan bound (§Perf): the gather paths pay for the
        full ``max_chain`` table width even when live chains are short, so
        the budget tracks ``cluster_nblocks.max()`` bucketed to the next
        power of two — exact results, one recompile per bucket growth."""
        live = max(1, int(self.state.cluster_nblocks.max()))
        b = 1
        while b < live:
            b *= 2
        return min(b, self.cfg.max_chain)

    def _search_fn(self, nprobe: int, k: int, budget: int):
        key = (nprobe, k, self.cfg.search_path, self.cfg.use_kernel, budget,
               self.cfg.rerank)
        if key not in self._search_fns:
            score_fn = None
            if self.cfg.payload == "pq":
                # state-free: centroids come from the traced state argument,
                # so cached search fns never pin a stale pool copy
                score_fn = pqmod.pq_score_fn(
                    self.pq, use_kernel=self.cfg.use_kernel
                )
            self._search_fns[key] = make_search_fn(
                self.pool_cfg,
                nprobe=nprobe,
                k=k,
                path=self.cfg.search_path,
                score_fn=score_fn,
                chain_budget=budget,
                pq=self.pq,
                rerank=self.cfg.rerank,
            )
        return self._search_fns[key]

    def search(self, queries, nprobe=None, k=None):
        """Returns (dists [Q, k], ids [Q, k]); ids are -1 past corpus end."""
        assert self.state is not None
        nprobe = nprobe or self.cfg.nprobe
        k = k or self.cfg.k
        q = jnp.asarray(queries, jnp.float32)
        d, i = self._search_fn(nprobe, k, self._chain_budget())(self.state, q)
        return np.asarray(d), np.asarray(i)

    # ------------------------------------------------------ rearrange ----
    def maybe_rearrange(self, max_passes: int = 4) -> int:
        """Compact offender chains until quiescent; returns #passes run."""
        n = 0
        for _ in range(max_passes):
            self.state, triggered = self._rearrange_fn(self.state)
            if not bool(triggered):
                break
            n += 1
        return n

    @property
    def ntotal(self) -> int:
        return int(self.state.num_vectors)


def _assign_blockwise(x: jax.Array, cents: jax.Array, chunk: int = 8192):
    """Memory-bounded argmin assignment for large training sets."""
    outs = []
    cn = jnp.sum(cents * cents, axis=1)
    for i in range(0, x.shape[0], chunk):
        xc = x[i : i + chunk]
        d = cn[None] - 2.0 * xc @ cents.T
        outs.append(jnp.argmin(d, axis=1))
    return jnp.concatenate(outs)


def build_ivf(
    x: np.ndarray,
    *,
    n_clusters: int,
    payload: str = "flat",
    pq_m: int = 0,
    block_size: int = 1024,
    capacity_vectors: Optional[int] = None,
    add_batch: int = 65536,
    **kw,
) -> IVFIndex:
    """Offline build: train + replay the corpus through batched inserts."""
    cfg = IVFIndexConfig(
        n_clusters=n_clusters,
        dim=x.shape[1],
        payload=payload,
        pq_m=pq_m,
        block_size=block_size,
        capacity_vectors=capacity_vectors or 2 * len(x),
        **kw,
    )
    index = IVFIndex(cfg)
    index.train(x)
    for i in range(0, len(x), add_batch):
        index.add(x[i : i + add_batch])
    return index
