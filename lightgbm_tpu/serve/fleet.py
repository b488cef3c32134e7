"""Multi-tenant model fleet: stacked packed serving + replicated dispatch.

The production shape of the paper's workload is one binary classifier
per cache node / segment / window generation, ALL live at once under
query traffic: the LRB-style harness retrains every window while the
previous generation keeps answering (PAPER.md; PAPERS.md "LRB").  A
solo :class:`~.packed.PackedEnsemble` serves ONE booster per jitted
program, so a fleet of M tenants would mean M servers, M program
families and M cold swaps.  This module extends the packed layout's
tree axis by a **model axis** instead:

* :class:`PackedFleet` stacks M same-shape-family boosters into one
  ``(M, T, N)`` array family (split/threshold-hi-lo/children/cat-bitset
  /leaf tables; static aux gains ``num_tenants``), so ONE jitted depth
  scan serves any ``(tenant_ids, rows)`` batch with a per-row tenant
  gather — routing is byte-identical per tenant to its solo
  ``PackedEnsemble`` because both kernels share
  :func:`~.packed.route_left`;
* a tenant **hot-swap is a device index write**
  (``lax.dynamic_update_slice`` on the model axis): when the incoming
  booster fits the fleet's pad family nothing retraces, so one tenant
  can retrain through the pipeline (PR 7) while the other M-1 keep
  answering from the same compiled program;
* :class:`FleetServer` adds **device-replicated dispatch**: the fleet
  arrays are replicated onto N local devices (the same local mesh
  ``ops/shard.py`` trains over), request micro-batch queues round-robin
  across the replicas, and each replica degrades to the host tree walk
  independently through its own
  :class:`~lightgbm_tpu.robust.retry.CircuitBreaker` — one dead chip
  dims one replica, not the fleet;
* an opt-in **bf16-quantized value variant** (``value_dtype="bf16"``)
  halves the leaf-table bytes: routing stays exact (the hi/lo
  threshold compare is untouched), only the leaf VALUES quantize —
  mirroring the training-side int8 contract (routing exact, values
  quantize; docs/Serving.md).

Telemetry (``serve.fleet.*``, docs/Observability.md): ``swap`` timing,
``swaps`` / ``swap_shape_changes`` / ``requests`` / ``rows`` /
``device_batches`` / ``device_failures`` / ``fallback_requests``
counters, per-tenant ``tenant.<m>.rows`` dispatch counters, and the
``replica_queue_depth.<r>`` / ``replica_degraded.<r>`` /
``degraded_replicas`` gauges.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from queue import Empty, Queue
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..obs import tracing
from ..robust import faults
from ..robust.retry import CircuitBreaker
from ..utils.log import LightGBMError, log_warning
from .engine import ModelMeta, _as_gbdt
from .packed import (PackedEnsemble, _prepare_rows, pack_ensemble,
                     route_left, row_bucket, tree_slice)

__all__ = ["PackedFleet", "FleetServer", "TenantHandle", "pack_fleet",
           "fleet_predict_scores", "fleet_predict_leaves"]

#: accepted ``value_dtype`` spellings -> jnp dtype of the leaf table
_VALUE_DTYPES = {"f32": jnp.float32, "float32": jnp.float32,
                 "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16}


def _value_dtype(name: str):
    try:
        return _VALUE_DTYPES[str(name).lower()]
    except KeyError:
        raise LightGBMError(
            f"unknown fleet value_dtype {name!r}; expected one of "
            f"{sorted(set(_VALUE_DTYPES))}") from None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedFleet:
    """M stacked :class:`~.packed.PackedEnsemble` tenants as one pytree.

    Every array is the solo layout with a leading model axis —
    ``(M, T, N)`` node tables, ``(M, T, L)`` leaf values, ``(M, W)``
    categorical bitset words, ``(M, T)`` stump flags.  Tenants whose
    solo pads are smaller than the fleet pads are padded up (padding
    trees are stumps with leaf value 0, padded nodes are unreachable),
    which leaves per-tenant results untouched.  The static aux
    (``num_tenants``, ``num_model``, ``max_depth``, ``num_features``,
    ``value_dtype``) rides in the treedef: equal pads AND equal aux hit
    the same jit cache entry — the index-write hot-swap zero-retrace
    contract.
    """

    split_feature: jnp.ndarray
    threshold_hi: jnp.ndarray
    threshold_lo: jnp.ndarray
    decision_type: jnp.ndarray
    left_child: jnp.ndarray
    right_child: jnp.ndarray
    cat_start: jnp.ndarray
    cat_len: jnp.ndarray
    cat_words: jnp.ndarray
    leaf_value: jnp.ndarray
    is_stump: jnp.ndarray
    num_tenants: int = 1
    num_model: int = 1
    max_depth: int = 0
    num_features: int = 1
    value_dtype: str = "f32"

    _ARRAY_FIELDS = ("split_feature", "threshold_hi", "threshold_lo",
                     "decision_type", "left_child", "right_child",
                     "cat_start", "cat_len", "cat_words", "leaf_value",
                     "is_stump")

    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in self._ARRAY_FIELDS)
        aux = (self.num_tenants, self.num_model, self.max_depth,
               self.num_features, self.value_dtype)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def tree_pad(self) -> int:
        return int(self.split_feature.shape[1])

    @property
    def node_pad(self) -> int:
        return int(self.split_feature.shape[2])

    @property
    def word_pad(self) -> int:
        return int(self.cat_words.shape[1])

    def shape_signature(self) -> tuple:
        """Hashable pad-family signature: a tenant swap between equal
        signatures re-dispatches into already-compiled programs."""
        return (self.split_feature.shape, self.leaf_value.shape,
                self.cat_words.shape, self.num_model, self.max_depth,
                self.num_features, self.value_dtype)

    def fits(self, pe: PackedEnsemble) -> bool:
        """Can ``pe`` be index-written into this fleet without growing
        any pad?  (The zero-retrace swap precondition.)"""
        return (pe.split_feature.shape[0] <= self.tree_pad
                and pe.split_feature.shape[1] <= self.node_pad
                and pe.cat_words.shape[0] <= self.word_pad
                and pe.max_depth <= self.max_depth
                and pe.num_model == self.num_model
                and pe.num_features == self.num_features)


def _padded_tenant_arrays(pe: PackedEnsemble, t_pad: int, n_pad: int,
                          w_pad: int, leaf_dtype) -> Tuple:
    """The solo pack's arrays padded up to the fleet pads, in
    ``PackedFleet._ARRAY_FIELDS`` order (without the leading model
    axis).  Padding trees are stumps (leaf 0 value 0 — a zero
    contribution), padded nodes/words are never reached."""
    dt = int(t_pad) - int(pe.split_feature.shape[0])
    dn = int(n_pad) - int(pe.split_feature.shape[1])
    dw = int(w_pad) - int(pe.cat_words.shape[0])
    if min(dt, dn, dw) < 0:
        raise LightGBMError("packed ensemble exceeds the fleet pads")

    def pad2(a, fill=0):
        return jnp.pad(a, ((0, dt), (0, dn)), constant_values=fill)

    return (
        pad2(pe.split_feature), pad2(pe.threshold_hi),
        pad2(pe.threshold_lo), pad2(pe.decision_type),
        pad2(pe.left_child, -1), pad2(pe.right_child, -1),
        pad2(pe.cat_start), pad2(pe.cat_len),
        jnp.pad(pe.cat_words, (0, dw)),
        jnp.pad(pe.leaf_value, ((0, dt), (0, dn))).astype(leaf_dtype),
        jnp.pad(pe.is_stump, (0, dt), constant_values=True),
    )


def stack_packs(packs: Sequence[PackedEnsemble],
                value_dtype: str = "f32") -> PackedFleet:
    """Stack solo packs (equal ``num_model``/``num_features``) into one
    :class:`PackedFleet`, padding every tenant to the fleet-wide max of
    each pad dimension."""
    if not packs:
        raise LightGBMError("stack_packs needs at least one tenant")
    k = packs[0].num_model
    nf = packs[0].num_features
    for i, pe in enumerate(packs):
        if pe.num_model != k or pe.num_features != nf:
            raise LightGBMError(
                f"tenant {i} has num_model={pe.num_model}/num_features="
                f"{pe.num_features}; the fleet requires ({k}, {nf}) — "
                f"pack every tenant with the same num_features")
    t_pad = max(int(pe.split_feature.shape[0]) for pe in packs)
    n_pad = max(int(pe.split_feature.shape[1]) for pe in packs)
    w_pad = max(int(pe.cat_words.shape[0]) for pe in packs)
    depth = max(int(pe.max_depth) for pe in packs)
    dtype = _value_dtype(value_dtype)
    cols = [jnp.stack(col) for col in zip(*[
        _padded_tenant_arrays(pe, t_pad, n_pad, w_pad, dtype)
        for pe in packs])]
    return PackedFleet(*cols, num_tenants=len(packs), num_model=k,
                       max_depth=depth, num_features=nf,
                       value_dtype=str(value_dtype).lower())


def pack_fleet(boosters: Sequence, num_features: Optional[int] = None,
               start_iteration: int = 0, num_iteration: int = -1,
               value_dtype: str = "f32"
               ) -> Tuple[PackedFleet, List[PackedEnsemble]]:
    """Pack M boosters (``Booster`` / ``GBDT`` / model-file path each)
    into a fleet.  ``num_features`` defaults to the max over tenants so
    every tenant shares one query signature.  Returns the fleet AND the
    per-tenant solo packs (the byte-identity reference; callers may
    drop them)."""
    gbdts = [_as_gbdt(b) for b in boosters]
    for g in gbdts:
        g._flush_pending()
    nf = int(num_features) if num_features else \
        max(g.max_feature_idx + 1 for g in gbdts)
    # seed-then-specialize fleets pass the SAME booster M times
    # (LGBM_FleetCreate does); pack each distinct booster once
    packed_by_id = {}
    packs = []
    for g in gbdts:
        pe = packed_by_id.get(id(g))
        if pe is None:
            pe = pack_ensemble(g.models, g.num_model,
                               start_iteration=start_iteration,
                               num_iteration=num_iteration,
                               num_features=nf)
            packed_by_id[id(g)] = pe
        packs.append(pe)
    return stack_packs(packs, value_dtype), packs


# ---------------------------------------------------------------------------
# jitted kernels: per-row tenant gather traversal + model-axis index write
# ---------------------------------------------------------------------------


def _fleet_traverse(fl: PackedFleet, tid, xhi, xlo):
    """(R, T) leaf index per (row, tree) with a per-row tenant gather;
    identical decision math to the solo kernel (shared ``route_left``),
    so each row routes exactly as its tenant's solo pack would."""
    r, t = xhi.shape[0], fl.split_feature.shape[1]
    t_ix = jnp.arange(t, dtype=jnp.int32)[None, :]
    r_ix = jnp.arange(r, dtype=jnp.int32)[:, None]
    m_ix = tid[:, None]
    node0 = jnp.where(fl.is_stump[m_ix, t_ix], -1, 0).astype(jnp.int32)

    def body(node, _):
        act = node >= 0
        cur = jnp.maximum(node, 0)
        sf = fl.split_feature[m_ix, t_ix, cur]
        left = route_left(
            fl.decision_type[m_ix, t_ix, cur],
            fl.threshold_hi[m_ix, t_ix, cur],
            fl.threshold_lo[m_ix, t_ix, cur],
            fl.cat_len[m_ix, t_ix, cur],
            lambda widx: fl.cat_words[
                m_ix, fl.cat_start[m_ix, t_ix, cur] + widx],
            xhi[r_ix, sf], xlo[r_ix, sf])
        nxt = jnp.where(left, fl.left_child[m_ix, t_ix, cur],
                        fl.right_child[m_ix, t_ix, cur])
        return jnp.where(act, nxt, node), None

    node, _ = jax.lax.scan(body, node0, None, length=fl.max_depth)
    return ~node


@jax.jit
def _fleet_scores(fl: PackedFleet, tid, xhi, xlo):
    """(K, R) float32 raw scores — traverse + per-row tenant leaf
    gather + per-class sum, one fused program for any tenant mix.  The
    bf16 variant upcasts the gathered values before the f32 sum."""
    r, t = xhi.shape[0], fl.split_feature.shape[1]
    leaves = _fleet_traverse(fl, tid, xhi, xlo)
    t_ix = jnp.arange(t, dtype=jnp.int32)[None, :]
    vals = fl.leaf_value[tid[:, None], t_ix, leaves].astype(jnp.float32)
    per_class = vals.reshape(r, t // fl.num_model, fl.num_model)
    return per_class.sum(axis=1).T


@jax.jit
def _fleet_leaves(fl: PackedFleet, tid, xhi, xlo):
    """(R, T) int32 leaf index per (row, tree) — padding trees
    included; callers slice to their tenant's real tree count."""
    return _fleet_traverse(fl, tid, xhi, xlo)


@jax.jit
def _fleet_write(fl: PackedFleet, row: PackedFleet, idx):
    """Index-write one tenant (``row`` is a ``num_tenants=1`` fleet at
    the FLEET pads) into the model axis at ``idx`` — the hot-swap
    primitive.  ``idx`` is traced, so every tenant id shares one
    compiled program."""
    ch_f, aux = fl.tree_flatten()
    ch_r, _ = row.tree_flatten()
    out = tuple(
        jax.lax.dynamic_update_slice(
            a, b.astype(a.dtype), (idx,) + (0,) * (a.ndim - 1))
        for a, b in zip(ch_f, ch_r))
    return PackedFleet.tree_unflatten(aux, out)


_fleet_scores = obs.track_jit("serve.fleet.scores", _fleet_scores)
_fleet_leaves = obs.track_jit("serve.fleet.leaves", _fleet_leaves)
_fleet_write = obs.track_jit("serve.fleet.write", _fleet_write)


def _prepare_tenants(fl: PackedFleet, tenant_ids, rows: int,
                     pad_rows: int) -> jnp.ndarray:
    """Validate + row-pad the per-row tenant ids (scalar broadcasts)."""
    tid = np.asarray(tenant_ids, np.int32)
    if tid.ndim == 0:
        tid = np.full(rows, int(tid), np.int32)
    if tid.shape != (rows,):
        raise LightGBMError(
            f"tenant_ids shape {tid.shape} does not match {rows} rows")
    if rows and (tid.min() < 0 or tid.max() >= fl.num_tenants):
        raise LightGBMError(
            f"tenant_ids must be in [0, {fl.num_tenants}); got "
            f"[{tid.min()}, {tid.max()}]")
    if pad_rows > rows:
        tid = np.pad(tid, (0, pad_rows - rows))
    return jnp.asarray(tid)


def fleet_predict_scores(fl: PackedFleet, tenant_ids, data: np.ndarray,
                         bucket_rows: bool = True,
                         min_bucket: int = 128) -> np.ndarray:
    """Raw scores (num_model, rows) float64 for a mixed-tenant batch —
    ONE device dispatch regardless of how many tenants the batch
    touches."""
    n = int(np.asarray(data).shape[0])
    if n == 0:
        return np.zeros((fl.num_model, 0), np.float64)
    pad = row_bucket(n, min_bucket) if bucket_rows else n
    tid = _prepare_tenants(fl, tenant_ids, n, pad)
    xhi, xlo, n = _prepare_rows(fl, data, pad)
    obs.inc("serve.fleet.device_batches")
    out = _fleet_scores(fl, tid, xhi, xlo)
    return np.asarray(out, np.float64)[:, :n]


def fleet_predict_leaves(fl: PackedFleet, tenant_ids, data: np.ndarray,
                         bucket_rows: bool = True,
                         min_bucket: int = 128) -> np.ndarray:
    """Leaf index (rows, tree_pad) int32 for a mixed-tenant batch;
    columns past a tenant's real tree count are padding."""
    n = int(np.asarray(data).shape[0])
    if n == 0:
        return np.zeros((0, fl.tree_pad), np.int32)
    pad = row_bucket(n, min_bucket) if bucket_rows else n
    tid = _prepare_tenants(fl, tenant_ids, n, pad)
    xhi, xlo, n = _prepare_rows(fl, data, pad)
    obs.inc("serve.fleet.device_batches")
    return np.asarray(_fleet_leaves(fl, tid, xhi, xlo), np.int32)[:n]


# ---------------------------------------------------------------------------
# FleetServer: replicated dispatch + per-tenant hot swap
# ---------------------------------------------------------------------------


class _FleetGen:
    """One immutable generation of the served fleet: the per-replica
    device copies plus per-tenant metadata (output conversion + the
    degrade path's host trees)."""

    __slots__ = ("fleets", "metas")

    def __init__(self, fleets: Tuple[PackedFleet, ...],
                 metas: Tuple[ModelMeta, ...]):
        self.fleets = fleets
        self.metas = metas

    @property
    def fleet(self) -> PackedFleet:
        return self.fleets[0]


class _Replica:
    """One dispatch replica: a device, a micro-batch queue, and an
    independent circuit breaker so degradation is per-replica."""

    __slots__ = ("index", "device", "queue", "worker", "breaker")

    def __init__(self, index: int, device, breaker: CircuitBreaker):
        self.index = index
        self.device = device
        self.queue: Queue = Queue()
        self.worker: Optional[threading.Thread] = None
        self.breaker = breaker


class FleetServer:
    """Thread-safe multi-tenant hot-swap predictor over a
    :class:`PackedFleet`, replicated across local devices.

    ``boosters`` seeds the M tenants (each a ``Booster``/``GBDT``/model
    path; seed a cold fleet by repeating one booster M times and
    ``swap_tenant``-ing later).  ``replicas`` picks how many local
    devices hold a fleet copy (0 = all local devices); request
    dispatch round-robins across them.  ``value_dtype="bf16"`` opts
    into the quantized leaf-value variant (routing exact, values
    ~3 decimal digits).  ``num_iteration``/``start_iteration`` select
    the served slice, applied on every swap, exactly like
    :class:`~.engine.PredictionServer`.
    """

    def __init__(self, boosters: Sequence, *, num_iteration: int = -1,
                 start_iteration: int = 0, min_bucket: int = 128,
                 replicas: int = 1, max_batch: int = 8192,
                 max_wait_ms: float = 2.0, host_fallback: bool = True,
                 value_dtype: str = "f32",
                 num_features: Optional[int] = None,
                 breaker_factory=None):
        from .. import compile_cache
        compile_cache.configure()
        if not boosters:
            raise LightGBMError("FleetServer needs at least one tenant")
        self.num_iteration = int(num_iteration)
        self.start_iteration = int(start_iteration)
        self.min_bucket = int(min_bucket)
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.host_fallback = bool(host_fallback)
        self.value_dtype = str(value_dtype).lower()
        _value_dtype(self.value_dtype)   # validate early
        devices = jax.local_devices()
        n_rep = int(replicas) or len(devices)
        if n_rep < 1:
            raise LightGBMError(f"replicas must be >= 1, got {replicas}")
        # more replicas than devices: wrap around (separate queues and
        # breakers still isolate load/poison even on a shared chip)
        self._devices = [devices[i % len(devices)] for i in range(n_rep)]
        if breaker_factory is None:
            breaker_factory = lambda i: CircuitBreaker(  # noqa: E731
                failure_threshold=3, reprobe_interval_s=2.0)
        self._replicas = [_Replica(i, d, breaker_factory(i))
                          for i, d in enumerate(self._devices)]
        self._lock = threading.Lock()        # generation pointer
        self._swap_lock = threading.Lock()   # serializes swaps
        self._stopping = threading.Event()
        self._rr = 0

        gbdts = [_as_gbdt(b) for b in boosters]
        fleet, packs = pack_fleet(
            gbdts, num_features=num_features,
            start_iteration=self.start_iteration,
            num_iteration=self.num_iteration,
            value_dtype=self.value_dtype)
        metas = tuple(self._meta_for(g, pe)
                      for g, pe in zip(gbdts, packs))
        self._gen = _FleetGen(self._replicate(fleet), metas)
        obs.set_gauge("serve.fleet.tenants", fleet.num_tenants)
        obs.set_gauge("serve.fleet.replicas", n_rep)
        # anchor the rolling timeline at 0 dark replicas: without it a
        # first degradation mid-window would integrate as a full-window
        # outage in the SLO's dark fraction (obs/slo.py)
        obs.set_gauge("serve.fleet.degraded_replicas", 0)

    # -- construction helpers -------------------------------------------
    def _meta_for(self, gbdt, pe: PackedEnsemble) -> ModelMeta:
        host_trees = None
        if self.host_fallback:
            host_trees = list(tree_slice(
                gbdt.models, gbdt.num_model, self.start_iteration,
                self.num_iteration))
        return ModelMeta(gbdt, pe.num_iterations, host_trees,
                         pe.num_model)

    def _replicate(self, fleet: PackedFleet) -> Tuple[PackedFleet, ...]:
        return tuple(jax.device_put(fleet, d) for d in self._devices)

    # -- introspection --------------------------------------------------
    def _snapshot(self) -> _FleetGen:
        with self._lock:
            return self._gen

    @property
    def fleet(self) -> PackedFleet:
        return self._snapshot().fleet

    @property
    def num_tenants(self) -> int:
        return self._snapshot().fleet.num_tenants

    @property
    def num_replicas(self) -> int:
        return len(self._replicas)

    def degraded_replicas(self) -> List[int]:
        """Indices of replicas whose breaker is currently open."""
        return [r.index for r in self._replicas
                if r.breaker.state == "open"]

    def tenant(self, tenant_id: int) -> "TenantHandle":
        """A single-tenant view with the ``PredictionServer`` surface
        (``swap``/``predict``/``warmup``) — the pipeline's tenant-aware
        swap target (docs/Pipeline.md)."""
        return TenantHandle(self, tenant_id)

    # -- tenant hot swap ------------------------------------------------
    def swap_tenant(self, tenant_id: int, booster) -> bool:
        """Atomically replace ONE tenant.  Packing and the device index
        write happen outside the generation lock; readers only ever see
        complete generations.  Returns True when the new model fits the
        fleet's pad family — the zero-retrace index-write case; False
        means a pad grew and the whole fleet was re-padded (one-off
        retrace, like a solo swap that changes shape)."""
        m = int(tenant_id)
        gbdt = _as_gbdt(booster)
        with obs.span("serve.fleet.swap", cat="serve", tenant=m), \
                self._swap_lock:
            gen = self._snapshot()
            fl = gen.fleet
            if not 0 <= m < fl.num_tenants:
                raise LightGBMError(
                    f"tenant_id {m} out of range [0, {fl.num_tenants})")
            gbdt._flush_pending()
            pe = pack_ensemble(gbdt.models, gbdt.num_model,
                               start_iteration=self.start_iteration,
                               num_iteration=self.num_iteration,
                               num_features=fl.num_features)
            if pe.num_model != fl.num_model:
                raise LightGBMError(
                    f"tenant {m} booster has num_model={pe.num_model}; "
                    f"the fleet serves num_model={fl.num_model}")
            fits = fl.fits(pe)
            t_pad = max(fl.tree_pad, int(pe.split_feature.shape[0]))
            n_pad = max(fl.node_pad, int(pe.split_feature.shape[1]))
            w_pad = max(fl.word_pad, int(pe.cat_words.shape[0]))
            depth = max(fl.max_depth, int(pe.max_depth))
            dtype = _value_dtype(fl.value_dtype)
            row = PackedFleet(
                *(a[None] for a in _padded_tenant_arrays(
                    pe, t_pad, n_pad, w_pad, dtype)),
                num_tenants=1, num_model=fl.num_model, max_depth=depth,
                num_features=fl.num_features,
                value_dtype=fl.value_dtype)
            idx = np.int32(m)
            fleets = []
            for rep, cur in zip(self._replicas, gen.fleets):
                if not fits:
                    cur = self._grow_pads(cur, t_pad, n_pad, w_pad,
                                          depth)
                rrow = jax.device_put(row, rep.device)
                fleets.append(_fleet_write(cur, rrow, idx))
            metas = list(gen.metas)
            metas[m] = self._meta_for(gbdt, pe)
            # captured inside the serve.fleet.swap span: this tenant's
            # request spans link through the swap to the training
            # window above it (obs/tracing.py)
            metas[m].train_ctx = tracing.capture()
            new_gen = _FleetGen(tuple(fleets), tuple(metas))
            with self._lock:
                self._gen = new_gen
        obs.inc("serve.fleet.swaps")
        obs.inc(f"serve.fleet.tenant.{m}.swaps")
        if not fits:
            obs.inc("serve.fleet.swap_shape_changes")
        return fits

    @staticmethod
    def _grow_pads(fl: PackedFleet, t_pad: int, n_pad: int, w_pad: int,
                   depth: int) -> PackedFleet:
        """Re-pad every tenant of ``fl`` up to the new pad family (the
        shape-change swap path; a retrace follows by construction)."""
        dt = t_pad - fl.tree_pad
        dn = n_pad - fl.node_pad
        dw = w_pad - fl.word_pad

        def pad3(a, fill=0):
            return jnp.pad(a, ((0, 0), (0, dt), (0, dn)),
                           constant_values=fill)

        return PackedFleet(
            pad3(fl.split_feature), pad3(fl.threshold_hi),
            pad3(fl.threshold_lo), pad3(fl.decision_type),
            pad3(fl.left_child, -1), pad3(fl.right_child, -1),
            pad3(fl.cat_start), pad3(fl.cat_len),
            jnp.pad(fl.cat_words, ((0, 0), (0, dw))),
            jnp.pad(fl.leaf_value, ((0, 0), (0, dt), (0, dn))),
            jnp.pad(fl.is_stump, ((0, 0), (0, dt)),
                    constant_values=True),
            num_tenants=fl.num_tenants, num_model=fl.num_model,
            max_depth=depth, num_features=fl.num_features,
            value_dtype=fl.value_dtype)

    # -- warmup ---------------------------------------------------------
    def warmup(self, row_buckets: Optional[Sequence[int]] = None
               ) -> List[int]:
        """Precompile the fleet traversal for each pow2 row bucket on
        EVERY replica, plus the index-write program (so the first real
        ``swap_tenant`` is zero-retrace too).  ``None`` warms the
        standard small-batch ladder."""
        if row_buckets is None:
            row_buckets = [128, 1024, 8192]
        gen = self._snapshot()
        nf = gen.fleet.num_features
        done: List[int] = []
        for rows in row_buckets:
            b = row_bucket(int(rows), self.min_bucket)
            if b in done:
                continue
            with obs.span("serve.fleet.warmup", cat="serve", rows=b):
                zeros = np.zeros((b, nf))
                for rep, fl in zip(self._replicas, gen.fleets):
                    fleet_predict_scores(fl, 0, zeros, min_bucket=b)
            done.append(b)
        # identity re-write of tenant 0 compiles the swap program per
        # replica; the result is discarded, the generation is untouched
        for rep, fl in zip(self._replicas, gen.fleets):
            ch, aux = fl.tree_flatten()
            row = PackedFleet.tree_unflatten(
                (1,) + aux[1:], tuple(a[:1] for a in ch))
            _fleet_write(fl, row, np.int32(0))
        return done

    # -- prediction -----------------------------------------------------
    def _pick_replica(self) -> _Replica:
        with self._lock:
            i = self._rr
            self._rr = (i + 1) % len(self._replicas)
        return self._replicas[i]

    def _host_raw(self, gen: _FleetGen, tid: np.ndarray,
                  data: np.ndarray) -> np.ndarray:
        """(K, rows) float64 via each tenant's host tree walk — the
        per-replica degrade path (byte-identical to the tenant's
        ``Booster.predict`` raw accumulation)."""
        out = np.zeros((gen.fleet.num_model, data.shape[0]), np.float64)
        for m in np.unique(tid):
            meta = gen.metas[int(m)]
            if meta.host_trees is None:
                raise LightGBMError(
                    "fleet host fallback unavailable (host_fallback "
                    "was disabled)")
            rows = np.nonzero(tid == m)[0]
            out[:, rows] = meta.host_raw(data[rows])
        return out

    def _score_batch(self, rep: _Replica, gen: _FleetGen,
                     tid: np.ndarray, data: np.ndarray) -> np.ndarray:
        """(K, rows) raw scores on one replica with per-replica
        degradation: device kernel when the replica's breaker allows,
        the host tree walk when dispatch fails or the breaker is open.
        Input errors raise immediately and never count as device
        faults."""
        fl = gen.fleets[rep.index]
        if data.shape[1] < fl.num_features:
            # input fault: fails the request, never the availability
            # SLO (obs/slo.py) nor the breaker
            obs.inc("serve.fleet.input_errors")
            raise LightGBMError(
                f"query data has {data.shape[1]} features but the "
                f"fleet needs {fl.num_features}")
        err: Optional[BaseException] = None
        if rep.breaker.allow():
            try:
                faults.check("serve.fleet.dispatch")
                raw = fleet_predict_scores(fl, tid, data,
                                           min_bucket=self.min_bucket)
            except Exception as e:   # noqa: BLE001 — degrade, not drop
                err = e
            else:
                dark = rep.breaker.record_success()
                if dark is not None:
                    obs.observe("serve.fleet.degraded_time", dark)
                    self._record_degraded(rep, 0)
                    log_warning(
                        f"fleet replica {rep.index}: device path "
                        f"recovered after {dark:.3f} s degraded")
                obs.inc("serve.fleet.ok")
                return raw
        if not self.host_fallback:
            obs.inc("serve.fleet.failed")
            if err is not None:
                raise err
            raise LightGBMError(
                f"fleet replica {rep.index}: device path unavailable "
                f"(circuit open) and host fallback is disabled")
        out = self._host_raw(gen, tid, data)
        if err is not None:
            obs.inc("serve.fleet.device_failures")
            if rep.breaker.record_failure():
                self._record_degraded(rep, 1)
                log_warning(
                    f"fleet replica {rep.index}: device dispatch "
                    f"failing ({err!r}); circuit open — serving host "
                    f"fallback, re-probing every "
                    f"{rep.breaker.reprobe_interval_s:g} s")
        obs.inc("serve.fleet.fallback_requests")
        return out

    def _record_degraded(self, rep: _Replica, value: int) -> None:
        obs.set_gauge(f"serve.fleet.replica_degraded.{rep.index}", value)
        obs.set_gauge("serve.fleet.degraded_replicas",
                      len(self.degraded_replicas()))

    def _convert(self, gen: _FleetGen, tid: np.ndarray, raw: np.ndarray,
                 raw_score: bool) -> np.ndarray:
        """Per-tenant output conversion (objective / RF averaging) of a
        mixed batch: each tenant's rows get exactly what its solo
        server would return."""
        k = gen.fleet.num_model
        n = raw.shape[1]
        tenants = np.unique(tid)
        if len(tenants) == 1:
            return gen.metas[int(tenants[0])].convert(raw, raw_score)
        out = np.empty(n if k == 1 else (n, k), np.float64)
        for m in tenants:
            rows = np.nonzero(tid == m)[0]
            out[rows] = gen.metas[int(m)].convert(raw[:, rows],
                                                  raw_score)
        return out

    def predict(self, tenant_ids, data, raw_score: bool = False,
                replica: Optional[int] = None) -> np.ndarray:
        """Score a mixed-tenant batch — one device dispatch on one
        replica (round-robin unless ``replica`` pins it), each row
        answered exactly as its tenant's solo server would.  Output
        matches ``Booster.predict`` per row: (rows,) for single-model
        tenants, (rows, num_model) for multiclass."""
        data = np.atleast_2d(np.asarray(data, np.float64))
        n = int(data.shape[0])
        gen = self._snapshot()
        tid = np.asarray(tenant_ids, np.int32)
        if tid.ndim == 0:
            tid = np.full(n, int(tid), np.int32)
        # input faults, not device faults: fail the REQUEST before any
        # dispatch so neither the breaker nor the host fallback sees a
        # malformed batch (counted apart from availability, obs/slo.py)
        if tid.shape != (n,):
            obs.inc("serve.fleet.input_errors")
            raise LightGBMError(
                f"tenant_ids shape {tid.shape} does not match {n} rows")
        if n and (tid.min() < 0 or tid.max() >= gen.fleet.num_tenants):
            obs.inc("serve.fleet.input_errors")
            raise LightGBMError(
                f"tenant_ids must be in [0, {gen.fleet.num_tenants}); "
                f"got [{tid.min()}, {tid.max()}]")
        rep = (self._replicas[int(replica)] if replica is not None
               else self._pick_replica())
        with obs.span("serve.fleet.predict", cat="serve", rows=n,
                      replica=rep.index) as sp:
            if n and tracing.enabled() and int(tid.min()) == \
                    int(tid.max()):
                # single-tenant batch: link to the training window of
                # the one model generation answering it (mixed batches
                # have no single lineage to name)
                ctx = gen.metas[int(tid[0])].train_ctx
                if ctx is not None:
                    sp.set(tenant=int(tid[0]),
                           model_trace_id=ctx.trace_id,
                           model_span_id=ctx.span_id)
            raw = self._score_batch(rep, gen, tid, data)
            out = self._convert(gen, tid, raw, raw_score)
        obs.inc("serve.fleet.requests")
        obs.inc("serve.fleet.rows", n)
        if obs.enabled():
            for m, c in zip(*np.unique(tid, return_counts=True)):
                obs.inc(f"serve.fleet.tenant.{int(m)}.rows", int(c))
        return out

    # -- micro-batching across replicas ---------------------------------
    def start(self) -> "FleetServer":
        """Start one micro-batching worker per replica (idempotent)."""
        with self._lock:
            self._stopping.clear()
            for rep in self._replicas:
                if rep.worker is not None and rep.worker.is_alive():
                    continue
                rep.worker = threading.Thread(
                    target=self._drain_loop, args=(rep,),
                    name=f"lgbm-fleet-{rep.index}", daemon=True)
                rep.worker.start()
        return self

    def stop(self) -> None:
        with self._lock:
            workers = [rep.worker for rep in self._replicas]
            for rep in self._replicas:
                rep.worker = None
            # set the flag INSIDE the lock: submit() holds it across
            # its liveness check + enqueue, so every accepted request
            # is in a queue its worker will still drain before exiting
            self._stopping.set()
        for w in workers:
            if w is not None:
                w.join(timeout=10.0)

    def __enter__(self) -> "FleetServer":
        return self.start()

    def __exit__(self, *exc) -> bool:
        self.stop()
        return False

    def submit(self, tenant_ids, data,
               raw_score: bool = False) -> Future:
        """Enqueue a (tenant_ids, rows) request on the next replica's
        micro-batch queue (round-robin); resolves to what ``predict``
        would return for those rows."""
        data = np.atleast_2d(np.asarray(data, np.float64))
        tid = np.asarray(tenant_ids, np.int32)
        if tid.ndim == 0:
            tid = np.full(data.shape[0], int(tid), np.int32)
        fut: Future = Future()
        rep = self._pick_replica()
        # liveness check + enqueue under the lock stop() sets
        # _stopping under: a request accepted here is guaranteed a
        # worker that drains its queue before exiting (no Future can
        # be orphaned by a concurrent stop())
        with self._lock:
            if (self._stopping.is_set() or rep.worker is None
                    or not rep.worker.is_alive()):
                raise LightGBMError("fleet micro-batching workers not "
                                    "running; call start() (or "
                                    "predict())")
            # the submitter's trace context rides the queue item to the
            # replica worker (None while tracing is off)
            rep.queue.put((tid, data, bool(raw_score), fut,
                           time.perf_counter(), tracing.capture()))
        obs.set_gauge(f"serve.fleet.replica_queue_depth.{rep.index}",
                      rep.queue.qsize())
        return fut

    def _drain_loop(self, rep: _Replica) -> None:
        while True:
            try:
                first = rep.queue.get(timeout=0.05)
            except Empty:
                if self._stopping.is_set():
                    return
                continue
            batch = [first]
            rows = first[1].shape[0]
            deadline = time.perf_counter() + self.max_wait_ms / 1e3
            while rows < self.max_batch:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                try:
                    item = rep.queue.get(timeout=remaining)
                except Empty:
                    break
                batch.append(item)
                rows += item[1].shape[0]
            obs.set_gauge(f"serve.fleet.replica_queue_depth.{rep.index}",
                          rep.queue.qsize())
            self._run_batch(rep, batch)

    def _run_batch(self, rep: _Replica, batch: List[Tuple]) -> None:
        now = time.perf_counter()
        for _, _, _, _, t0, _ in batch:
            obs.observe("serve.fleet.queue_wait", now - t0)
        for flavor in sorted({rs for _, _, rs, _, _, _ in batch}):
            group = [b for b in batch if b[2] == flavor]
            try:
                if len(group) > 1:
                    tid = np.concatenate([g[0] for g in group])
                    data = np.concatenate([g[1] for g in group], axis=0)
                else:
                    tid, data = group[0][0], group[0][1]
                out = self.predict(tid, data, raw_score=flavor,
                                   replica=rep.index)
            except Exception:   # noqa: BLE001 — isolate the poison
                # one poisoned submit fails only its OWN Future
                # (docs/Robustness.md): retry each request alone
                obs.inc("serve.fleet.poisoned_batches")
                for g in group:
                    try:
                        res = self.predict(g[0], g[1], raw_score=flavor,
                                           replica=rep.index)
                    except Exception as e:   # noqa: BLE001
                        if not g[3].done():
                            g[3].set_exception(e)
                    else:
                        if not g[3].done():
                            g[3].set_result(res)
                continue
            lo = 0
            for g in group:
                hi = lo + g[1].shape[0]
                if not g[3].done():
                    g[3].set_result(out[lo:hi])
                lo = hi
        done = time.perf_counter()
        for _, data, _, fut, t0, ctx in batch:
            if (fut.done() and not fut.cancelled()
                    and fut.exception() is None):
                obs.observe("serve.fleet.request_latency", done - t0)
                if ctx is not None:
                    # submit -> replica flush causal edge, parented
                    # under the submitter's active span
                    obs.span_event(
                        "serve.fleet.request", t0, done - t0,
                        cat="serve", rows=int(data.shape[0]),
                        replica=rep.index,
                        span_id=tracing.new_id(),
                        trace_id=ctx.trace_id,
                        **({"parent_id": ctx.span_id}
                           if ctx.span_id else {}))


class TenantHandle:
    """One tenant of a :class:`FleetServer` behind the solo
    ``PredictionServer`` surface (``swap``/``predict``/``warmup``/
    ``_model``), so the retrain pipeline — or any other solo-server
    client — can target a fleet tenant without knowing about fleets."""

    __slots__ = ("fleet_server", "tenant_id")

    def __init__(self, fleet_server: FleetServer, tenant_id: int):
        m = int(tenant_id)
        if not 0 <= m < fleet_server.num_tenants:
            raise LightGBMError(
                f"tenant_id {m} out of range "
                f"[0, {fleet_server.num_tenants})")
        self.fleet_server = fleet_server
        self.tenant_id = m

    @property
    def _model(self) -> Optional[ModelMeta]:
        return self.fleet_server._snapshot().metas[self.tenant_id]

    def swap(self, booster) -> bool:
        return self.fleet_server.swap_tenant(self.tenant_id, booster)

    def predict(self, data, raw_score: bool = False) -> np.ndarray:
        return self.fleet_server.predict(self.tenant_id, data,
                                         raw_score=raw_score)

    def warmup(self, row_buckets: Optional[Sequence[int]] = None
               ) -> List[int]:
        return self.fleet_server.warmup(row_buckets)

    def stop(self) -> None:
        """No-op: the fleet's replicas outlive any one tenant view."""
