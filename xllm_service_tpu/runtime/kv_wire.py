"""Cross-process device-to-device KV migration (SURVEY.md §2.3, §5.8).

The reference's PD data plane is engine-side NCCL: the service hands out
``k_cache_ids``/``v_cache_ids``/cluster addresses and the engines move KV
blocks GPU-to-GPU (SURVEY.md §2.3 "Distributed comm backend"). The TPU
equivalent here is ``jax.experimental.transfer`` — a PJRT-level
cross-process transfer server that moves device buffers over TCP without
bouncing them through Python bytes, HTTP bodies, or host numpy.

Topology: the *prefill* worker runs one process-wide ``TransferServer``
and stages the exported ``[L, P, ps, Hkv, Dh]`` K/V block under a fresh
uuid; the control handshake (uuid + server address + aval) rides the
existing ``/kv/import`` HTTP message; the *decode* worker connects back
and pulls the block straight into its own devices, then scatters it into
its pool. Transport failure on either side degrades to the host-shuttle
raw-bytes path (``worker._serve_pd_prefill``), so the wire is an
optimization, never a new failure mode — but never a silent one: the
degrade is logged at WARNING once, and which transport moved each block
is on ``/metrics``
(``xllm_worker_kv_migration_{device_wire,chunked,direct}_total``).

Support is probed once per process with a loopback self-pull: a backend
whose PJRT client cannot serve transfers (it lacks
``CreateBuffersForAsyncHostToDevice``) fails the probe and the worker
keeps the host shuttle. ``XLLM_KV_DEVICE_WIRE=0`` forces it off.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, Optional, Tuple

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_wire: Optional["DeviceWire"] = None
_unsupported = False


class WireUnsupported(RuntimeError):
    """This process's backend cannot serve/receive device transfers —
    a permanent condition the peer should remember."""


class WireNoPull(RuntimeError):
    """The pull failed before any transfer started — the staged block is
    provably untouched, so the offering side can safely drain it."""


class DeviceWire:
    """Process-wide staging server for outbound KV blocks."""

    def __init__(self) -> None:
        import jax
        from jax.experimental import transfer

        client = jax.local_devices()[0].client
        # Without an explicit transport address the server only builds
        # LOCAL (same-process) bulk transports and CHECK-fails — hard
        # process abort — when a remote peer pulls; "host:0" makes it
        # bind a TCP bulk-transport socket too. Cross-host deployments
        # advertise a routable host via XLLM_KV_WIRE_HOST.
        host = os.environ.get("XLLM_KV_WIRE_HOST", "127.0.0.1")
        self._server = transfer.start_transfer_server(
            client, f"{host}:0", [f"{host}:0"])
        self.address: str = self._server.address()
        self._next_uuid = 1
        self._staged: Dict[int, Tuple[Any, Any]] = {}
        self.leaked = 0     # blocks pinned by un-drainable registrations
        self._mu = threading.Lock()
        self._self_check()

    def _self_check(self) -> None:
        """Loopback pull of a tiny array — raises where the backend
        cannot serve transfers, so the caller can disable the wire."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        probe = jnp.arange(8, dtype=jnp.float32)
        uuid = self.stage(probe, probe)
        try:
            k, v = _pull_via(self._server, {
                "addr": self.address, "uuid": uuid,
                "shape": list(probe.shape), "dtype": "float32"})
            if not np.array_equal(np.asarray(jax.device_get(k)),
                                  np.asarray(jax.device_get(v))):
                raise RuntimeError("loopback pull returned wrong data")
        finally:
            self.release(uuid)

    def stage(self, k: Any, v: Any) -> int:
        """Offer a K/V device-array pair for one remote pull; returns the
        uuid the peer must present. Hold a reference until release()."""
        with self._mu:
            uuid = self._next_uuid
            self._next_uuid += 1
            self._staged[uuid] = (k, v)
        self._server.await_pull(uuid, [k, v])
        return uuid

    def stage_one(self, arr: Any) -> int:
        """Offer a SINGLE device array for one remote pull (the EPD
        embedding handoff — docs/EPD.md). Same lifecycle contract as
        :meth:`stage`; release() handles the 1-tuple arity."""
        with self._mu:
            uuid = self._next_uuid
            self._next_uuid += 1
            self._staged[uuid] = (arr,)
        self._server.await_pull(uuid, [arr])
        return uuid

    def release(self, uuid: int, drain: bool = False,
                leaked: bool = False) -> None:
        """Drop the staged pair. ``await_pull`` has no cancel, so the
        server-side registration outlives this unless the peer pulled it:

        - peer pulled (success, or refusal after its pull): plain release;
        - ``drain=True``: the peer provably never started a pull — free
          the registration by self-pulling it (a second pull of a
          consumed uuid hangs, so this is only safe in that case);
        - ``leaked=True``: transfer state unknown (timeout mid-pull,
          pull error) — count it; the block stays pinned server-side.
        """
        with self._mu:
            entry = self._staged.pop(uuid, None)
        if entry is None:
            return
        if drain:
            k = entry[0]
            try:
                _pull_via(self._server, {
                    "addr": self.address, "uuid": uuid,
                    "shape": list(k.shape), "dtype": str(k.dtype)},
                    arity=len(entry))
            except Exception as e:  # noqa: BLE001 — drain is best effort
                logger.warning("device-wire drain of uuid %d failed (%s);"
                               " block stays pinned", uuid, e)
                with self._mu:
                    self.leaked += 1
        elif leaked:
            with self._mu:
                self.leaked += 1
            logger.warning("device-wire uuid %d abandoned mid-transfer; "
                           "block stays pinned (%d leaked so far)",
                           uuid, self.leaked)

    def staged_count(self) -> int:
        with self._mu:
            return len(self._staged)


def get_device_wire() -> Optional[DeviceWire]:
    """The process's staging server, or None when gated off or the
    backend failed the loopback probe. First call pays the probe."""
    global _wire, _unsupported
    if os.environ.get("XLLM_KV_DEVICE_WIRE", "auto") in ("0", "off"):
        return None
    with _lock:
        if _wire is None and not _unsupported:
            try:
                _wire = DeviceWire()
                logger.info("kv device wire up at %s", _wire.address)
            except Exception as e:  # noqa: BLE001 — unsupported backend
                # Once per process: _unsupported stops further probes.
                logger.warning("kv device wire unavailable (%s: %s); "
                               "every KV migration of this process "
                               "takes the host shuttle",
                               type(e).__name__, e)
                _unsupported = True
        return _wire


def _pull_via(server: Any, tr: Dict[str, Any], arity: int = 2) -> Tuple:
    """Pull the staged array tuple described by the ``transfer``
    handshake dict into this process's devices, via ``server``'s
    connection pool. ``arity`` matches the staged tuple: 2 for K/V
    pairs, 1 for single-array (embedding) tickets — the avals presented
    to pull() must agree with what await_pull registered."""
    import jax
    import jax.numpy as jnp

    conn = server.connect(tr["addr"])
    shape = tuple(int(s) for s in tr["shape"])
    dtype = jnp.dtype(str(tr["dtype"]))
    sharding = jax.sharding.SingleDeviceSharding(jax.local_devices()[0])
    aval = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return tuple(conn.pull(int(tr["uuid"]), [aval] * arity))


def peek_device_wire() -> Optional["DeviceWire"]:
    """The wire if it already exists — NO probe/creation side effects
    (metrics scrapes must never initialize a transfer server)."""
    return _wire


def pull_block(tr: Dict[str, Any]) -> Tuple[Any, Any]:
    """Decode-side: pull a staged (k, v) pair described by the
    ``transfer`` handshake dict. The exception type tells the offering
    side what to do with its staged block: WireUnsupported → remember
    the peer can never pull; WireNoPull → safe to drain; anything else →
    transfer state unknown (treat the block as pinned)."""
    wire = get_device_wire()
    if wire is None:
        raise WireUnsupported("device wire disabled on this backend")
    try:
        conn = wire._server.connect(tr["addr"])
    except Exception as e:  # noqa: BLE001 — no transfer started yet
        raise WireNoPull(f"connect to {tr.get('addr')} failed: {e}")
    import jax
    import jax.numpy as jnp

    try:
        shape = tuple(int(s) for s in tr["shape"])
        dtype = jnp.dtype(str(tr["dtype"]))
        sharding = jax.sharding.SingleDeviceSharding(
            jax.local_devices()[0])
        aval = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    except Exception as e:  # noqa: BLE001 — still before the pull
        raise WireNoPull(f"bad transfer ticket: {e}")
    k, v = conn.pull(int(tr["uuid"]), [aval, aval])
    return k, v


def pull_one(tr: Dict[str, Any]) -> Any:
    """Requester side of a single-array (embedding) ticket: same
    exception contract as :func:`pull_block`, one array back."""
    wire = get_device_wire()
    if wire is None:
        raise WireUnsupported("device wire disabled on this backend")
    try:
        conn = wire._server.connect(tr["addr"])
    except Exception as e:  # noqa: BLE001 — no transfer started yet
        raise WireNoPull(f"connect to {tr.get('addr')} failed: {e}")
    import jax
    import jax.numpy as jnp

    try:
        shape = tuple(int(s) for s in tr["shape"])
        dtype = jnp.dtype(str(tr["dtype"]))
        sharding = jax.sharding.SingleDeviceSharding(
            jax.local_devices()[0])
        aval = jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    except Exception as e:  # noqa: BLE001 — still before the pull
        raise WireNoPull(f"bad transfer ticket: {e}")
    (arr,) = conn.pull(int(tr["uuid"]), [aval])
    return arr
