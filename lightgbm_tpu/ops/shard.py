"""Single-controller data-parallel sharding for the device grower.

The reference's Network layer (PAPER.md §1) powers its data-parallel
tree learner with Allreduce/ReduceScatter collectives where the
histogram reduction is the ONLY synchronization point per split.  The
multiprocess worker mesh (``lightgbm_tpu/parallel/``) reproduces that
faithfully but dispatches per-worker Python every step, which keeps it
out of ``DeviceGrower.fused_train``'s K-trees-per-dispatch ``lax.scan``
— and therefore out of every fused-path win (program cache, int8 MXU
histograms, persisted stage plans).

This module is the jax-native equivalent: ONE Python process shards the
binned matrix (and every per-row buffer) row-wise across a device mesh
with ``shard_map``, the existing fused scan runs unchanged on every
chip, and a ``lax.psum`` of the wave histograms over the mesh axis is
the sole cross-device communication of the growth loop (plus one (2,)
``pmax`` per tree for the global quantization scale when
``grad_quant_bits=8``).  Partition, traversal and leaf bookkeeping stay
shard-local; find-best runs replicated on the globally-reduced
histograms, so every device grows the identical tree — no split
broadcast, exactly like the reference's data-parallel learner with
``GLOBAL_data_count``.

Row layout (the :class:`ShardSpec` contract)
--------------------------------------------

Global padded row space = ``n_shards * local_rows``; shard ``d`` owns
the block ``[d * local_rows, (d + 1) * local_rows)``.  The real rows
are DEALT EVENLY (:func:`shard_span`): shard ``d`` holds the contiguous
real rows ``[start_d, start_d + count_d)`` at the front of its block,
counts differ by at most one, and the bucket pad lies at each block's
tail.  A shard's histogram costs what its own real and live rows cost,
and every wave ends in a psum, so the mesh runs at the pace of its
fullest shard: with the pad dealt evenly no shard waits for another.
:func:`shard_span` is the ONE place that knows the deal — the traced
per-shard cutoff (:func:`local_valid_rows`), the canonical draws
(:func:`slice_global_draw`: row ``r`` still reads element ``r``), the
uploads, the multi-controller host blocks (:func:`process_row_span`)
and the per-row state between dispatches (:class:`DealtRows`) read it.

Determinism / byte-identity contract (docs/Sharding.md)
-------------------------------------------------------

* ``grad_quant_bits=8`` under the int32 find-best scan: integer psum is
  associative-exact, the quantization scale is a global ``pmax`` (max is
  exact), the stochastic-rounding noise and the in-scan bagging mask
  are drawn at CANONICAL GLOBAL shapes (``draw_npad`` / ``bag_npad`` —
  jax's threefry draw is NOT prefix-stable across shapes, so the shape
  itself is part of the stream) and sliced per shard, and the leaf
  refit runs on exact int32 digit sums — so the sharded trainer emits
  models BYTE-IDENTICAL to the single-device fused path.
* f32 / bf16 histograms: the psum's reduction order is fixed by the
  compiled program, so results are bit-reproducible run-to-run but not
  bitwise equal to the single-device accumulation order.  Counts psum
  as int32 either way, so row counts stay exact past 2^24 global rows.
* find-best inside the wave (ops/grow.py) composes with all of the
  above: the psum happens INSIDE the wave's program, directly between
  the shard-local wave histograms and the replicated gain scan that
  consumes them — the reduced stack is scanned where it lands instead
  of round-tripping through HBM first.  The 1-vs-N byte-identity
  contract is pinned by tests/_shard_worker.py's ``fused_find``
  scenario.
"""

from __future__ import annotations

import os
import threading
from typing import NamedTuple, Optional, Tuple

import jax
import numpy as np

from ..utils.log import LightGBMError, log_info

#: the one mesh axis the sharded grower reduces over
SHARD_AXIS = "shards"

#: env fallbacks for the multi-controller bring-up params (one process
#: per host cannot share a config file edit per rank, so rank/host
#: count usually travel through the launcher's environment)
ENV_COORDINATOR = "LGBM_TPU_COORDINATOR"
ENV_NUM_HOSTS = "LGBM_TPU_NUM_HOSTS"
ENV_HOST_RANK = "LGBM_TPU_HOST_RANK"


def shard_map_nocheck(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication (vma) checking off: the
    replicated-output contract of the grower's growth loop is enforced
    by the byte-identity tests instead (tests/test_shard.py,
    scripts/check_shard.py)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class ShardSpec(NamedTuple):
    """Static facts of one sharded-training layout (trace-level; joins
    the grower program-cache signature via ``shard_signature``)."""

    n_shards: int     #: mesh size D (always > 1; D == 1 runs unsharded)
    axis: str         #: mesh axis name (SHARD_AXIS)
    global_rows: int  #: REAL global row count (num_valid upper bound)
    #: canonical global shape of the quantization-noise draw — the
    #: single-device grower's chunk pad for ``global_rows``, so the
    #: per-row rounding noise matches the unsharded path bit-for-bit
    draw_npad: int
    #: canonical global shape of the bagging uniform draw
    #: (= ``histogram.bucket_size(global_rows)``, the same pad the
    #: serial learner's bagging buffer uses)
    bag_npad: int


def shard_span(num_rows, n_shards: int, d):
    """``(start, count)``: the real rows ``[start, start + count)`` that
    shard ``d`` of ``n_shards`` holds of ``num_rows`` — THE row layout.
    The first ``num_rows % n_shards`` shards hold one row more than the
    others, spans follow one another in shard order and cover every row
    once.  Plain integer arithmetic, so ``num_rows`` and ``d`` may be
    Python ints or traced int32 scalars (no product passes
    ``num_rows``)."""
    base = num_rows // n_shards
    rem = num_rows - base * n_shards
    over = d - rem
    # d * base + min(d, rem), written without a host-or-device `min`
    start = d * base + d - (over + abs(over)) // 2
    return start, base + (over < 0)


def row_spans(num_rows: int, n_shards: int, local_rows: int):
    """Host side of :func:`shard_span`: ``[(start, count)] * n_shards``
    for ``num_rows`` real rows in blocks of ``local_rows`` padded rows
    (shard ``d``'s row ``start_d + j`` sits at padded index
    ``d * local_rows + j``)."""
    spans = [tuple(int(v) for v in shard_span(int(num_rows),
                                              int(n_shards), d))
             for d in range(int(n_shards))]
    if max(c for _, c in spans) > int(local_rows):
        raise LightGBMError(
            f"{num_rows} rows over {n_shards} shards need "
            f"{max(c for _, c in spans)} rows a shard, the block holds "
            f"{local_rows}")
    return spans


def local_valid_rows(spec: ShardSpec, local_rows: int, num_valid):
    """Traced per-shard valid-row count: this shard's share of the
    ``num_valid`` real rows under the even deal; they fill the front of
    its block."""
    import jax.numpy as jnp
    _, count = shard_span(num_valid, spec.n_shards,
                          jax.lax.axis_index(spec.axis))
    return jnp.clip(count, 0, local_rows).astype(jnp.int32)


def slice_global_draw(spec: ShardSpec, full, local_rows: int):
    """Take this shard's rows of a canonically-shaped global draw.

    ``full`` is a 1-D array drawn at a canonical global shape
    (``draw_npad`` / ``bag_npad``) and indexed by GLOBAL real row: the
    shard's block starts at its first real row's element, so row ``r``
    reads element ``r`` whatever the mesh size.  Elements behind the
    shard's last real row (the next shard's, or nothing) fall on bucket
    padding, which the valid mask zeroes.
    """
    import jax.numpy as jnp
    total = spec.n_shards * local_rows
    if full.shape[0] >= total:
        full = full[:total]
    else:
        full = jnp.pad(full, (0, total - full.shape[0]))
    off, _ = shard_span(spec.global_rows, spec.n_shards,
                        jax.lax.axis_index(spec.axis))
    return jax.lax.dynamic_slice(full, (off,), (local_rows,))


def make_shard_mesh(num_devices: int = 0):
    """One-axis ``SHARD_AXIS`` mesh over local devices (0 = all).

    Raises :class:`LightGBMError` when fewer than 2 devices are
    available — single-controller sharding with one device is exactly
    the unsharded fused path, so callers fall back instead.
    """
    from jax.sharding import Mesh
    devices = jax.devices()
    d = int(num_devices) or len(devices)
    if d < 2:
        raise LightGBMError(
            f"data_sharding=single_controller needs >= 2 devices, have "
            f"{len(devices)} (request {d}); on CPU force a virtual mesh "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count=N")
    if d > len(devices):
        raise LightGBMError(
            f"shard_devices={d} exceeds available devices "
            f"({len(devices)})")
    return Mesh(np.asarray(devices[:d]), (SHARD_AXIS,))


def sharding_mode(config) -> str:
    """Resolved ``data_sharding`` mode string ("off" when unset)."""
    return str(getattr(config, "data_sharding", "off") or "off").lower()


# ---------------------------------------------------------------------------
# multi-controller (pod-slice) bring-up
# ---------------------------------------------------------------------------

def multihost_params(config=None) -> Optional[Tuple[str, int, int]]:
    """Resolve ``(coordinator_address, num_hosts, host_rank)`` from the
    config with ``LGBM_TPU_COORDINATOR`` / ``LGBM_TPU_NUM_HOSTS`` /
    ``LGBM_TPU_HOST_RANK`` env fallbacks.

    Returns None when none of the three is set anywhere (multi-
    controller simply not configured); raises :class:`LightGBMError`
    when the triple is only partially specified or malformed — a pod
    host guessing its rank would train a silently-wrong model.
    """
    coord = str(getattr(config, "coordinator_address", "") or ""
                ) or os.environ.get(ENV_COORDINATOR, "")
    hosts_raw = getattr(config, "num_hosts", 0) or 0
    hosts = int(hosts_raw) or int(os.environ.get(ENV_NUM_HOSTS, "0")
                                  or "0")
    rank_raw = getattr(config, "host_rank", -1)
    rank = int(-1 if rank_raw is None else rank_raw)
    if rank < 0:
        rank = int(os.environ.get(ENV_HOST_RANK, "-1") or "-1")
    if not coord and hosts <= 0 and rank < 0:
        return None
    if not coord or hosts <= 0 or rank < 0:
        raise LightGBMError(
            f"data_sharding=multi_controller needs ALL of "
            f"coordinator_address/num_hosts/host_rank (or the "
            f"{ENV_COORDINATOR}/{ENV_NUM_HOSTS}/{ENV_HOST_RANK} env "
            f"vars); resolved coordinator={coord!r} num_hosts={hosts} "
            f"host_rank={rank}")
    if rank >= hosts:
        raise LightGBMError(
            f"host_rank={rank} out of range for num_hosts={hosts}")
    if ":" not in coord:
        raise LightGBMError(
            f"coordinator_address must be host:port, got {coord!r}")
    return coord, hosts, rank


def _distributed_client_active() -> bool:
    """Whether ``jax.distributed.initialize`` already ran in this
    process — checked WITHOUT touching ``jax.devices()`` (which would
    initialize the backend pre-coordinator and wedge the bring-up)."""
    try:
        from jax._src import distributed as _jdist
        return getattr(_jdist.global_state, "client", None) is not None
    except Exception:   # noqa: BLE001 — private-API drift: assume cold
        return False


def multihost_setup(config=None) -> Tuple[int, int]:
    """Fail-fast ``jax.distributed`` bring-up for one pod-slice host.

    Returns ``(host_rank, num_hosts)``.  Idempotent: a process whose
    distributed client is already up just reports its rank.  Rank 0
    hosts the coordinator and initializes directly; ranks > 0 first
    probe the coordinator socket with :func:`~lightgbm_tpu.parallel.
    network.wait_for_peer` (honoring ``network_timeout`` /
    ``network_retries``) so a dead coordinator surfaces as the
    familiar "peer unreachable after N attempts" error instead of a
    multi-minute initialize hang.  On CPU the cross-process collective
    backend is pinned to gloo BEFORE initialize — without it every
    psum dies with "Multiprocess computations aren't implemented on
    the CPU backend".
    """
    from .. import obs
    if _distributed_client_active():
        rank = int(jax.process_index())
        hosts = int(jax.process_count())
        obs.set_gauge("shard.hosts", hosts)
        return rank, hosts
    resolved = multihost_params(config)
    if resolved is None:
        raise LightGBMError(
            "data_sharding=multi_controller: no coordinator configured "
            "— set coordinator_address/num_hosts/host_rank (or the "
            "LGBM_TPU_COORDINATOR/LGBM_TPU_NUM_HOSTS/"
            "LGBM_TPU_HOST_RANK env vars)")
    coord, hosts, rank = resolved
    # scoped to the CPU backend; a no-op for TPU pods
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    if rank > 0:
        # fail fast with peer context before the (slow) initialize
        # handshake; the probe retries with the shared backoff policy
        from ..parallel.network import wait_for_peer
        wait_for_peer(coord, config=config)
    from ..parallel.network import network_policy_from_config
    attempts, timeout_s = network_policy_from_config(config)
    try:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=hosts,
            process_id=rank,
            initialization_timeout=max(10, int(attempts * timeout_s)))
    except Exception as e:   # noqa: BLE001 — any bring-up failure
        raise LightGBMError(
            f"jax.distributed bring-up failed for host {rank}/{hosts} "
            f"against coordinator {coord}: {type(e).__name__}: {e}")
    got = int(jax.process_count())
    if got != hosts:
        raise LightGBMError(
            f"pod bring-up inconsistent: num_hosts={hosts} configured "
            f"but jax.process_count()={got}")
    obs.set_gauge("shard.hosts", hosts)
    log_info(f"multi_controller: host {rank}/{hosts} up against "
             f"{coord}, {len(jax.devices())} global device(s)")
    return rank, hosts


def is_multihost() -> bool:
    """True when this process is part of an initialized multi-process
    runtime (safe to call pre-bring-up: never initializes jax)."""
    if not _distributed_client_active():
        return False
    try:
        return int(jax.process_count()) > 1
    except Exception:   # noqa: BLE001
        return False


def mesh_is_multihost(mesh) -> bool:
    """Whether a mesh spans more than one process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def make_pod_mesh():
    """One-axis ``SHARD_AXIS`` mesh over ALL global devices, sorted by
    ``(process_index, device id)`` so each host's addressable devices
    form one CONTIGUOUS run of mesh positions — the invariant that
    makes a host's row block ``[first_dev * n_loc, (last_dev+1) *
    n_loc)`` contiguous in the global padded row space (and therefore
    loadable as one streamed slab)."""
    from jax.sharding import Mesh
    devices = sorted(jax.devices(),
                     key=lambda d: (int(d.process_index), int(d.id)))
    if len(devices) < 2:
        raise LightGBMError(
            f"data_sharding=multi_controller needs >= 2 global "
            f"devices, have {len(devices)}")
    return Mesh(np.asarray(devices), (SHARD_AXIS,))


def _process_devices(mesh, process_index: Optional[int] = None):
    """Mesh positions of one process's devices (a contiguous run)."""
    pid = (int(jax.process_index()) if process_index is None
           else int(process_index))
    idx = [i for i, d in enumerate(mesh.devices.flat)
           if int(d.process_index) == pid]
    if not idx:
        raise LightGBMError(
            f"process {pid} owns no devices of the pod mesh")
    if idx != list(range(idx[0], idx[0] + len(idx))):
        raise LightGBMError(
            f"pod mesh devices of process {pid} are not contiguous "
            f"(mesh positions {idx}); build the mesh with "
            f"make_pod_mesh()")
    return idx


def process_row_span(mesh, local_rows: int,
                     process_index: Optional[int] = None
                     ) -> Tuple[int, int]:
    """``[lo, hi)`` block of the global PADDED row space owned by one
    process under a pod mesh with ``local_rows`` rows per device."""
    idx = _process_devices(mesh, process_index)
    return idx[0] * int(local_rows), (idx[-1] + 1) * int(local_rows)


def process_real_rows(mesh, num_rows: int, local_rows: int,
                      process_index: Optional[int] = None):
    """Where one process's REAL rows go inside its padded block:
    ``[(real_lo, real_hi, local_offset)]``, one entry a device — global
    real rows ``[real_lo, real_hi)`` fill the host block from row
    ``local_offset`` on (:func:`shard_span`'s deal; the rest of each
    device's ``local_rows`` is pad)."""
    idx = _process_devices(mesh, process_index)
    spans = row_spans(num_rows, int(mesh.devices.size), local_rows)
    return [(spans[d][0], spans[d][0] + spans[d][1],
             (d - idx[0]) * int(local_rows)) for d in idx]


def shard_local_rows(num_data: int, n_shards: int, config,
                     row_bucketing: Optional[bool] = None) -> int:
    """Per-device padded row count for a ``num_data``-row dataset over
    ``n_shards`` devices: ``ceil(N/D)`` lifted onto the pow2 bucket
    ladder (unless quantization keys its rounding stream on the exact
    padded shape, or the bucket would cross the striped-count bound),
    then chunk-aligned.  Factored out of the grower so ingest code can
    compute a host's row block BEFORE the grower exists — the padded
    layout is part of the data contract, not a grower detail."""
    from .grow import _CHUNK, _ceil_to, COUNT_SPLIT_ROWS
    from .histogram import bucket_size
    if row_bucketing is None:
        row_bucketing = bool(getattr(config, "train_row_bucketing",
                                     True))
    quant_on = bool(int(getattr(config, "grad_quant_bits", 0) or 0))
    srows = -(-int(num_data) // int(n_shards))
    if row_bucketing and not quant_on:
        b = bucket_size(max(srows, 1))
        if b >= 2 * COUNT_SPLIT_ROWS:
            log_info(
                f"train_row_bucketing: per-shard bucket {b} would "
                f"reach the striped-count bound; using exact "
                f"per-shard rows ({srows})")
        else:
            srows = b
    return _ceil_to(max(srows, _CHUNK), _CHUNK)


# mesh programs (the transposing placement, the deal and its inverse)
# keyed by mesh device ids and shape: ONE compiled program per mesh,
# reused across growers/windows so warm same-shape windows re-dispatch
# instead of re-tracing (obs.track_jit makes any violation visible to
# the zero-retrace gates)
_TRANSPOSE_CACHE: dict = {}
_DEAL_CACHE: dict = {}
_PROGRAM_CACHE_LOCK = threading.Lock()


def transpose_col_sharded(mesh, axis: str = SHARD_AXIS):
    """Jitted ``(N, G) -> (G, N)`` transpose whose output is pinned
    column-split over the mesh axis — the multi-controller equivalent
    of the single-process ``device_put`` placement (``device_put``
    cannot reshard an array it cannot fully address; an SPMD program
    with explicit ``out_shardings`` can)."""
    key = (tuple(int(d.id) for d in mesh.devices.flat), axis)
    fn = _TRANSPOSE_CACHE.get(key)
    if fn is None:
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from .. import obs
        fn = obs.track_jit(
            "shard.binned_t",
            jax.jit(lambda x: jnp.transpose(x),
                    out_shardings=NamedSharding(mesh, P(None, axis))))
        with _PROGRAM_CACHE_LOCK:
            fn = _TRANSPOSE_CACHE.setdefault(key, fn)
    return fn


def host_replicated(mesh, value):
    """Place host-identical data fully-replicated on every device of a
    (possibly multi-process) mesh.  Every process must call this with
    the SAME value — it is the caller's broadcast contract (mappers
    and labels travel over the net.broadcast blob plane first)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P())
    arr = np.asarray(value)
    return jax.make_array_from_process_local_data(sh, arr)


class RowDeal:
    """Per-row arrays in a mesh's padded, row-sharded layout: the
    ``num_rows`` real rows dealt by :func:`shard_span` over blocks of
    ``local_rows``.  One object per sharded grower; the two jitted
    moves between row order and the dealt layout are cached per
    (mesh, shape) like the other mesh programs, so warm windows
    re-dispatch into them."""

    def __init__(self, mesh, axis: str, num_rows: int, local_rows: int):
        self.mesh, self.axis = mesh, axis
        self.num_rows, self.local_rows = int(num_rows), int(local_rows)
        self.n_shards = int(mesh.devices.size)
        self.spans = row_spans(num_rows, self.n_shards, local_rows)
        self.total = self.n_shards * self.local_rows
        self.multihost = mesh_is_multihost(mesh)

    def sharding(self, ndim: int = 1, row_axis: int = 0):
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = [None] * ndim
        spec[row_axis] = self.axis
        return NamedSharding(self.mesh, P(*spec))

    def is_dealt(self, a) -> bool:
        """Whether ``a`` is a per-row array already in this layout."""
        return (getattr(a, "ndim", 0) >= 1 and a.shape[0] == self.total
                and getattr(a, "sharding", None) == self.sharding(a.ndim))

    def _blocks(self, block_of):
        """Row-sharded ``(total, ...)`` array whose device ``d`` holds
        ``block_of(d)`` — each block goes from the host (or from where
        it lies) to its own device; nothing of ``total`` rows is ever
        on one device."""
        devs = list(self.mesh.devices.flat)
        mine = [d for d, dev in enumerate(devs)
                if dev.process_index == jax.process_index()]
        parts = [jax.device_put(block_of(d), devs[d]) for d in mine]
        shape = (self.total,) + tuple(parts[0].shape[1:])
        return jax.make_array_from_single_device_arrays(
            shape, self.sharding(len(shape)), parts)

    def place(self, rows):
        """Deal a ``(num_rows, ...)`` array (host, or device-resident)
        onto the mesh."""
        tail = [(0, 0)] * (rows.ndim - 1)

        def block_of(d):
            lo, cnt = self.spans[d]
            pad = [(0, self.local_rows - cnt)] + tail
            if isinstance(rows, np.ndarray):
                return np.pad(rows[lo:lo + cnt], pad)
            import jax.numpy as jnp
            return jnp.pad(rows[lo:lo + cnt], pad)

        return self._blocks(block_of)

    def place_blocks(self, local, first_device: int):
        """The same from a host block that is ALREADY dealt (a pod
        host's streamed ``(devices * local_rows, G)`` block)."""
        n = self.local_rows
        return self._blocks(
            lambda d: local[(d - first_device) * n:
                            (d - first_device + 1) * n])

    def _program(self, name: str, fn, out_sharding):
        key = (name, tuple(int(d.id) for d in self.mesh.devices.flat),
               self.num_rows, self.local_rows)
        prog = _DEAL_CACHE.get(key)
        if prog is None:
            from .. import obs
            prog = obs.track_jit(
                f"shard.{name}", jax.jit(fn, out_shardings=out_sharding))
            with _PROGRAM_CACHE_LOCK:
                prog = _DEAL_CACHE.setdefault(key, prog)
        return prog

    def deal(self, rows):
        """Device ``(num_rows,)`` in row order -> the dealt layout
        (static slices; the per-iteration sharded path's way in)."""
        if self.is_dealt(rows):
            return rows
        import jax.numpy as jnp
        n, spans = self.local_rows, self.spans

        def fn(x):
            return jnp.concatenate(
                [jnp.pad(x[lo:lo + cnt], (0, n - cnt))
                 for lo, cnt in spans])

        return self._program("deal", fn, self.sharding())(rows)

    def gather(self, dealt):
        """Dealt ``(total,)`` -> ``(num_rows,)`` in row order, on every
        device of the mesh."""
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        n, spans = self.local_rows, self.spans

        def fn(x):
            return jnp.concatenate(
                [x[d * n:d * n + cnt] for d, (_, cnt) in enumerate(spans)])

        return self._program("gather", fn,
                             NamedSharding(self.mesh, P()))(dealt)

    def to_host(self, dealt) -> np.ndarray:
        """Dealt ``(total,)`` -> host ``(num_rows,)`` in row order: each
        shard's real rows are copied off its own device (a pod host,
        which cannot address them all, reads the replicated gather)."""
        if self.multihost:
            return np.asarray(self.gather(dealt))
        n = self.local_rows
        out = np.empty((self.num_rows,) + tuple(dealt.shape[1:]),
                       dealt.dtype)
        for sh in dealt.addressable_shards:
            lo, cnt = self.spans[(sh.index[0].start or 0) // n]
            out[lo:lo + cnt] = np.asarray(sh.data)[:cnt]
        return out


class DealtRows:
    """The booster's ``(1, num_rows)`` training score while it lives
    dealt over the mesh between fused dispatches (``.dealt``, the
    ``(total,)`` row-sharded device array the next dispatch takes as it
    is).  Reading it as an array — ``np.asarray``, an index, a jnp
    operation — gives real rows in row order; nothing is moved until
    then, and ``block_until_ready`` waits on the dealt array alone."""

    def __init__(self, deal: RowDeal, dealt):
        self.deal, self.dealt = deal, dealt
        self._rows = None

    shape = property(lambda self: (1, self.deal.num_rows))
    dtype = property(lambda self: self.dealt.dtype)
    ndim = 2

    def block_until_ready(self):
        self.dealt.block_until_ready()
        return self

    def rows(self):
        """The ``(1, num_rows)`` device array in row order (kept)."""
        if self._rows is None:
            self._rows = self.deal.gather(self.dealt)[None, :]
        return self._rows

    def __array__(self, dtype=None, copy=None):
        out = self.deal.to_host(self.dealt)[None, :]
        return out if dtype is None else out.astype(dtype, copy=False)

    def __jax_array__(self):
        return self.rows()

    def __getitem__(self, idx):
        return self.rows()[idx]


def resolve_shard_mesh(config) -> Optional[object]:
    """Mesh for the configured ``data_sharding`` mode, or None.

    ``single_controller`` degrades gracefully (logged, training
    proceeds unsharded) — it is a local optimization.  A
    ``multi_controller`` failure RAISES instead: one pod host silently
    falling back to unsharded training while its peers wait on the
    histogram psum would wedge the whole slice, so bring-up errors
    must kill the process loudly.
    """
    mode = sharding_mode(config)
    if mode == "multi_controller":
        rank, hosts = multihost_setup(config)
        mesh = make_pod_mesh()
        log_info(f"data_sharding=multi_controller: host {rank}/{hosts}"
                 f", row-sharding over {mesh.devices.size} global "
                 f"device(s), psum wave histograms")
        return mesh
    if mode != "single_controller":
        return None
    try:
        mesh = make_shard_mesh(int(getattr(config, "shard_devices", 0)
                                   or 0))
    except LightGBMError as e:
        log_info(f"data_sharding=single_controller unavailable "
                 f"({e}); training unsharded")
        return None
    log_info(f"data_sharding=single_controller: row-sharding over "
             f"{mesh.devices.size} device(s), psum wave histograms")
    return mesh
