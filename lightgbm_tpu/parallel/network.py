"""Collective-communication verbs over a TPU device mesh.

TPU-native replacement for the reference's from-scratch collective layer
(``include/LightGBM/network.h:86-296``, ``src/network/network.cpp:64-315``:
Bruck / recursive-halving / ring algorithms over socket/MPI point-to-point
links).  On TPU none of that is re-implemented: the five verbs map directly
onto XLA collectives over a named mesh axis, and XLA lowers them to ICI
ring/tree collectives (DCN for multi-slice) — the literal hardware analog of
the reference's ``AllgatherRing``/``ReduceScatterRing``
(``network.cpp:212-226,299-314``).

Two usage levels:

* **inside ``shard_map``** — the learners call the ``Network.*`` verbs with
  data already device-local; these are thin ``jax.lax`` wrappers bound to
  the mesh axis name.
* **host level** — ``global_sum`` / ``sync_up_by_*`` mirror the reference's
  scalar syncs (``GlobalSyncUpByMin/Max/Mean``, ``network.h:165-257``) used
  by e.g. distributed seed/fraction agreement (``application.cpp:187-192``)
  and boost-from-average (``gbdt.cpp:300-309``).  In a single-controller
  JAX program every host already sees the same scalars, so these are
  identities kept for API parity — they become real collectives only under
  multi-controller ``jax.distributed``, where the caller feeds per-process
  values through ``psum`` via ``run_sharded``.

The reference's external-reduce-function hook (``LGBM_NetworkInitWithFunctions``,
``c_api.h:810``) lets an embedder supply its own transport; the analog here
is ``Network(mesh=...)`` accepting any existing ``jax.sharding.Mesh``.
"""

from __future__ import annotations

import functools
import socket
import struct
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..ops.shard import shard_map_nocheck
from ..robust import faults
from ..robust.retry import RetryError, RetryPolicy, with_retries
from ..utils.log import LightGBMError, log_info

AXIS = "workers"

#: bound ONCE at module scope: a per-call ``jax.jit`` builds a fresh
#: compile cache every invocation (recompiles every time) — the JL002
#: hazard the static analyzer flagged on the old inline form
_sum_leading_axis = obs.track_jit("net.global_sum",
                                  jax.jit(lambda a: a.sum(axis=0)))


def make_mesh(num_machines: int, devices=None) -> Mesh:
    """One-axis mesh over the first ``num_machines`` local devices."""
    if devices is None:
        devices = jax.devices()
    if num_machines > len(devices):
        raise LightGBMError(
            f"num_machines={num_machines} exceeds available devices "
            f"({len(devices)}); reduce num_machines or provision a larger "
            f"mesh")
    return Mesh(np.asarray(devices[:num_machines]), (AXIS,))


class Network:
    """A one-axis mesh + the reference's five collective verbs.

    The in-``shard_map`` verbs (psum/psum_scatter/all_gather/pmax/pmin) are
    static because they only bind the axis name; the mesh instance carries
    topology for the host-level helpers and sharding constructors.
    """

    def __init__(self, mesh: Optional[Mesh] = None, num_machines: int = 1,
                 devices=None):
        self.mesh = mesh if mesh is not None else make_mesh(num_machines,
                                                            devices)
        if len(self.mesh.axis_names) != 1:
            raise LightGBMError("Network expects a one-axis mesh; wrap "
                                "multi-axis meshes in a flat view")
        self.axis = self.mesh.axis_names[0]
        # trace-time comm accounting: every verb call below corresponds to
        # ONE collective op in the compiled program, so logging the
        # payload bytes at trace time records the per-execution comm
        # volume of each program (the analog of the reference's
        # "Network::Allreduce" buffer sizes) — used by tests and the
        # multichip dryrun to substantiate the O(total_bins) vs
        # O(2k*256) per-split claims.
        self.comm_log: list = []

    def _log(self, verb: str, x):
        try:
            nbytes = int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        except Exception:   # noqa: BLE001 — non-array payloads
            nbytes = 0
        self.comm_log.append((verb, nbytes))

    def reset_comm_log(self):
        self.comm_log = []

    @property
    def num_machines(self) -> int:
        return self.mesh.devices.size

    # -- in-shard_map verbs (Network::Allreduce etc.) -------------------
    def allreduce(self, x):
        """Sum-allreduce (HistogramBinEntry::SumReducer analog)."""
        self._log("allreduce", x)
        return jax.lax.psum(x, self.axis)

    def reduce_scatter(self, x):
        """Sum + scatter along leading axis (Network::ReduceScatter)."""
        self._log("reduce_scatter", x)
        return jax.lax.psum_scatter(x, self.axis, tiled=True)

    def all_gather(self, x):
        """Concatenate along a fresh leading axis (Network::Allgather)."""
        self._log("all_gather", x)
        return jax.lax.all_gather(x, self.axis)

    def allreduce_max(self, x):
        self._log("allreduce_max", x)
        return jax.lax.pmax(x, self.axis)

    def allreduce_min(self, x):
        self._log("allreduce_min", x)
        return jax.lax.pmin(x, self.axis)

    def rank(self):
        return jax.lax.axis_index(self.axis)

    def argmax_allreduce(self, key, payload, tie_id):
        """Pick the payload of the rank whose ``key`` is globally maximal,
        ties broken by the smaller ``tie_id`` — the SplitInfo max-reduce
        (``parallel_tree_learner.h:183-207``) as pmax/pmin + masked psum."""
        self._log("argmax_allreduce:key", key)
        self._log("argmax_allreduce:tie", tie_id)
        kmax = jax.lax.pmax(key, self.axis)
        is_max = key == kmax
        tid = jnp.where(is_max, tie_id, jnp.iinfo(jnp.int32).max)
        tmin = jax.lax.pmin(tid, self.axis)
        owner = is_max & (tie_id == tmin)

        def sel(v):
            self._log("argmax_allreduce:payload", v)
            return jax.lax.psum(
                jnp.where(owner, v.astype(jnp.float32), 0.0), self.axis)

        return jax.tree_util.tree_map(sel, payload), owner

    # -- sharding constructors ------------------------------------------
    def row_sharding(self):
        return NamedSharding(self.mesh, P(self.axis))

    def row2d_sharding(self):
        return NamedSharding(self.mesh, P(self.axis, None))

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def shard_rows(self, x):
        """Place a (D*k, ...) array so each device owns a contiguous k-row
        block (the pre-partitioned data distribution, ``dataset.h:82``)."""
        spec = P(self.axis, *([None] * (x.ndim - 1)))
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def replicate(self, x):
        return jax.device_put(x, self.replicated())

    # -- host-level scalar syncs (network.h:165-257) --------------------
    # Single-controller: every process sees the same host scalars, so these
    # are identities; kept so learner code reads like the reference.
    def sync_up_by_min(self, v):
        return v

    def sync_up_by_max(self, v):
        return v

    def sync_up_by_mean(self, v):
        return v

    def global_sum(self, x):
        """Sum a per-device-sharded array across the axis on host."""
        return _sum_leading_axis(x)

    # -- generic sharded runner -----------------------------------------
    def run_sharded(self, fn, in_specs, out_specs):
        """``jax.shard_map`` bound to this mesh/axis (replication
        checking off: the verb wrappers above make collective use
        explicit)."""
        return shard_map_nocheck(fn, self.mesh, in_specs, out_specs)


# ---------------------------------------------------------------------------
# fault-tolerant point-to-point helpers (the host-blob plane)
# ---------------------------------------------------------------------------
# XLA owns the on-device collectives above, but multi-controller
# bring-up still rides plain TCP: the jax.distributed coordinator
# handshake, and any embedder exchanging serialized mappers / machine
# lists over its own sockets (the reference's Linkers).  The reference
# blocks forever on a dead peer (linkers_socket.cpp Construct/Recv);
# these helpers bound every operation with a timeout and give connects
# capped-backoff retries, so a missing worker fails the mesh FAST and
# with context instead of hanging it (docs/Robustness.md).

DEFAULT_NETWORK_TIMEOUT_S = 30.0
DEFAULT_NETWORK_RETRIES = 5
#: recv_bytes length-prefix sanity bound: a corrupt/misbehaving peer
#: must produce a bounded protocol error, not a giant allocation
MAX_MESSAGE_BYTES = 1 << 30

_LEN_PREFIX = struct.Struct("<Q")


def connect_with_retries(host: str, port: int, *,
                         attempts: Optional[int] = None,
                         timeout_s: Optional[float] = None,
                         base_delay_s: float = 0.1,
                         config=None, sleep=time.sleep) -> socket.socket:
    """TCP connect with ``attempts`` bounded tries and capped
    exponential backoff; raises a clear "peer unreachable after N
    attempts" :class:`LightGBMError` instead of hanging the worker
    mesh.  The returned socket keeps ``timeout_s`` as its per-op
    timeout.  Explicit arguments win; otherwise ``config``'s
    ``network_retries`` / ``network_timeout`` params apply, then the
    schema defaults."""
    cfg_attempts, cfg_timeout = network_policy_from_config(config)
    if attempts is None:
        attempts = cfg_attempts
    if timeout_s is None:
        timeout_s = cfg_timeout
    attempts = max(int(attempts), 1)

    def attempt():
        faults.check("net.connect")
        return socket.create_connection((host, int(port)),
                                        timeout=float(timeout_s))

    policy = RetryPolicy(max_attempts=attempts,
                         base_delay_s=float(base_delay_s),
                         max_delay_s=2.0,
                         retry_on=(OSError, faults.InjectedFault))
    try:
        sock = with_retries(attempt, policy, site="net.connect",
                            sleep=sleep)
    except RetryError as e:
        raise LightGBMError(
            f"peer {host}:{port} unreachable after {attempts} "
            f"attempt{'s' if attempts != 1 else ''} (last error: "
            f"{e.__cause__!r}); check the machine list / coordinator "
            f"address and that the peer process is up") from e
    sock.settimeout(float(timeout_s))
    return sock


def wait_for_peer(address: str, *, attempts: Optional[int] = None,
                  timeout_s: Optional[float] = None,
                  base_delay_s: float = 0.1, config=None,
                  sleep=time.sleep) -> None:
    """Probe a ``host:port`` peer (e.g. the ``jax.distributed``
    coordinator) until it accepts a connection, then close — called
    BEFORE ``jax.distributed.initialize`` so a dead/mistyped
    coordinator fails fast with a clear error instead of stalling the
    whole mesh inside the runtime's own (much longer) handshake."""
    host, _, port = str(address).rpartition(":")
    if not host or not port.isdigit():
        raise LightGBMError(
            f"bad peer address {address!r} (expected host:port)")
    sock = connect_with_retries(host, int(port), attempts=attempts,
                                timeout_s=timeout_s,
                                base_delay_s=base_delay_s,
                                config=config, sleep=sleep)
    sock.close()


def _netop(sock: socket.socket, site: str, timeout_s: Optional[float],
           fn, what: str):
    """Shared wrapper for send/recv: fault site, optional per-op
    timeout override, and timeout/OS errors re-raised with context."""
    faults.check(site)
    if timeout_s is not None:
        sock.settimeout(float(timeout_s))
    try:
        return fn()
    except socket.timeout as e:
        peer = _peer_name(sock)
        raise LightGBMError(
            f"network timeout {what} {peer} (after "
            f"{sock.gettimeout():g} s); peer dead or partitioned — "
            f"the mesh should be rebuilt") from e
    except OSError as e:
        peer = _peer_name(sock)
        raise LightGBMError(f"network error {what} {peer}: {e}") from e


def _peer_name(sock: socket.socket) -> str:
    try:
        addr = sock.getpeername()
    except OSError:
        return "peer <unknown>"
    if isinstance(addr, tuple) and len(addr) >= 2:
        return f"peer {addr[0]}:{addr[1]}"
    return f"peer {addr!r}"     # AF_UNIX etc.


def send_bytes(sock: socket.socket, payload: bytes,
               timeout_s: Optional[float] = None) -> None:
    """Length-prefixed blocking send with a bounded timeout (the
    reference's ``Linkers::Send`` had none)."""
    def run():
        sock.sendall(_LEN_PREFIX.pack(len(payload)))
        sock.sendall(payload)
    _netop(sock, "net.send", timeout_s, run, "sending to")


def recv_bytes(sock: socket.socket,
               timeout_s: Optional[float] = None) -> bytes:
    """Length-prefixed blocking recv with a bounded timeout; a peer
    closing mid-message raises instead of returning a short read."""
    def read_exact(n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            # cap the per-call request so a large n never asks the
            # kernel for one giant buffer
            chunk = sock.recv(min(n - len(buf), 1 << 20))
            if not chunk:
                raise LightGBMError(
                    f"connection closed by {_peer_name(sock)} "
                    f"mid-message ({len(buf)}/{n} bytes)")
            buf.extend(chunk)
        return bytes(buf)

    def run():
        (length,) = _LEN_PREFIX.unpack(read_exact(_LEN_PREFIX.size))
        if length > MAX_MESSAGE_BYTES:
            # corrupt / torn / hostile prefix: a bounded protocol
            # error with context, never a giant allocation
            raise LightGBMError(
                f"{_peer_name(sock)} announced a {length}-byte message "
                f"(limit {MAX_MESSAGE_BYTES}); corrupt length prefix "
                f"or protocol mismatch")
        return read_exact(length)
    return _netop(sock, "net.recv", timeout_s, run, "receiving from")


def network_policy_from_config(config):
    """(attempts, timeout_s) from a Config's ``network_retries`` /
    ``network_timeout`` params (schema defaults otherwise)."""
    return (int(getattr(config, "network_retries",
                        DEFAULT_NETWORK_RETRIES)),
            float(getattr(config, "network_timeout",
                          DEFAULT_NETWORK_TIMEOUT_S)))


# ---------------------------------------------------------------------------
# pod-slice blob broadcast (rank 0 -> every peer)
# ---------------------------------------------------------------------------
# jax.distributed has no host-payload channel, and the mapper reference
# a pod host needs BEFORE it can bin its shard cannot ride a device
# collective (the mesh does not exist yet).  So the multi-controller
# ingest handshake reuses the length-prefixed blob plane above: rank 0
# serves the serialized payload on ``coordinator port + 1``, every peer
# dials it with the same retry/timeout policy as the coordinator probe.
# Rounds are SPMD-sequenced — every process calls broadcast_blob the
# same number of times in the same order — so one well-known port
# serves any number of sequential rounds.

#: offset from the jax.distributed coordinator port to the blob
#: broadcast port (the coordinator owns its own port on rank 0)
BROADCAST_PORT_OFFSET = 1


def pod_broadcast_address(coordinator_address: str) -> str:
    """``host:port`` of the blob broadcast endpoint derived from the
    coordinator address."""
    host, _, port = str(coordinator_address).rpartition(":")
    if not host or not port.isdigit():
        raise LightGBMError(
            f"bad coordinator address {coordinator_address!r} "
            f"(expected host:port)")
    return f"{host}:{int(port) + BROADCAST_PORT_OFFSET}"


def broadcast_blob(payload: Optional[bytes], *, address: str,
                   num_hosts: int, rank: int, config=None) -> bytes:
    """One broadcast round: rank 0 sends ``payload`` to every peer and
    returns it; peers pass ``payload=None`` and return the received
    bytes.  Fail-fast on both sides: rank 0 bounds the accept loop by
    the ``network_timeout``-derived deadline and names the ranks that
    never dialed in; peers ride ``connect_with_retries`` so a dead
    rank 0 surfaces as "peer unreachable after N attempts"."""
    faults.check("net.broadcast")
    attempts, timeout_s = network_policy_from_config(config)
    host, _, port = str(address).rpartition(":")
    if not host or not port.isdigit():
        raise LightGBMError(
            f"bad broadcast address {address!r} (expected host:port)")
    port = int(port)
    num_hosts = int(num_hosts)
    if int(rank) != 0:
        sock = connect_with_retries(host, port, config=config)
        try:
            send_bytes(sock, struct.pack("<i", int(rank)),
                       timeout_s=timeout_s)
            blob = recv_bytes(sock, timeout_s=timeout_s)
        finally:
            sock.close()
        obs.inc("net.broadcast_bytes", len(blob))
        return blob
    if payload is None:
        raise LightGBMError("broadcast_blob: rank 0 must supply the "
                            "payload")
    deadline = time.monotonic() + max(10.0, attempts * timeout_s)
    server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    pending = set(range(1, num_hosts))
    try:
        try:
            # peers dial the coordinator hostname; rank 0 accepts on
            # every interface so "localhost" vs the public name both
            # land here
            server.bind(("", port))
        except OSError as e:
            raise LightGBMError(
                f"broadcast endpoint {address} unavailable on host 0: "
                f"{e}") from e
        server.listen(max(num_hosts, 1))
        while pending:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise LightGBMError(
                    f"pod broadcast on {address}: host(s) "
                    f"{sorted(pending)} never connected within the "
                    f"network_timeout budget — peer dead at ingest "
                    f"bring-up")
            server.settimeout(min(remaining, 1.0))
            try:
                conn, _addr = server.accept()
            except socket.timeout:
                continue
            try:
                (peer_rank,) = struct.unpack(
                    "<i", recv_bytes(conn, timeout_s=timeout_s))
                send_bytes(conn, payload, timeout_s=timeout_s)
            finally:
                conn.close()
            pending.discard(peer_rank)
    finally:
        server.close()
    obs.inc("net.broadcast_bytes", len(payload))
    return payload


@functools.lru_cache(maxsize=8)
def _default_network(num_machines: int) -> Network:
    log_info(f"Initializing TPU collective mesh with {num_machines} "
             f"worker(s)")
    return Network(num_machines=num_machines)


def create_network(config, mesh: Optional[Mesh] = None) -> Network:
    """Network for a config: ``num_machines`` workers over local devices,
    or an externally supplied mesh (the LGBM_NetworkInitWithFunctions
    analog)."""
    if mesh is not None:
        return Network(mesh=mesh)
    return _default_network(int(config.num_machines))
