"""KV-migration transport probe: the BASELINE.md north star (KV GB/s).

Three transfer paths exist for PD disaggregation (SURVEY.md §7.3 item 1):

- **direct** — both engines live in one process on one host's devices;
  the exported page block stays a device array and lands in the decode
  pool via one donated scatter (``Engine.export_held(device=True)`` →
  ``Engine.import_sequence``). No host copy, no serialization.
- **host shuttle** — the cross-process wire path
  (device_get → meta+raw bytes → HTTP → frombuffer → device_put scatter,
  runtime/worker.py ``_serve_pd_prefill``/``_serve_kv_import``).
- **pipelined host shuttle** — the round-5 chunked variant of the same
  wire (worker ``_shuttle_send_chunks`` → ``/kv/chunk``): the block is
  sliced along L, every D2H copy starts async up front, and chunks
  stream host→device as their bytes land, overlapping the two
  directions.

``probe_kv_migration`` measures all three on the live hardware with
pool-layout-identical engines, so deployments can record
``kv_migration_gbps`` instead of guessing. The HTTP hop itself is not
simulated — the host path here measures the serialize/deserialize +
device roundtrip floor, an upper bound on what any loopback wire gives.
"""

from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.runtime.engine import Engine


def probe_kv_migration(src: Engine, dst: Engine, n_pages: int = 16,
                       iters: int = 5) -> Dict[str, float]:
    """Move an ``n_pages`` KV block src→dst via all three paths,
    ``iters`` timed reps each (one warmup). Engines must share pool
    layout. Returns {"bytes", "pages", "direct_gbps", "host_gbps",
    "host_pipelined_gbps"}."""
    ks = src.kv[0]
    if ks.shape[0:1] + ks.shape[2:] != \
            dst.kv[0].shape[0:1] + dst.kv[0].shape[2:]:
        raise ValueError("engines have different KV pool layouts")
    n_pages = min(n_pages, ks.shape[1] - 1, dst.kv[0].shape[1] - 1)
    if n_pages < 1:
        raise ValueError("pool too small to probe (needs >= 2 pages)")
    src_idx = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    dst_idx = jnp.arange(1, n_pages + 1, dtype=jnp.int32)
    # (k, v) blocks, as every transfer path speaks them (a single
    # latent pool's block stands for both: Engine._pages_out).
    nbytes = 2 * int(np.prod(ks[:, :n_pages].shape)) * ks.dtype.itemsize

    def _sync() -> None:
        # A host readback of a value that depends on the scatter is the
        # sync no backend can return early from. Read one written page
        # slice (64 KB-ish, negligible
        # next to the measured block) whose value depends on the scatter.
        # Index with the static int (n_pages == dst_idx[-1]): indexing
        # via the device array would add a second blocking readback to
        # every timed rep.
        np.asarray(jax.device_get(dst.kv[0][0, n_pages]))

    def direct_once() -> None:
        dst._pages_in(dst_idx, *src._pages_out(src_idx))
        _sync()

    def host_once() -> None:
        # The wire path: gather → host → bytes → host → device → scatter.
        k_host, v_host = (np.asarray(jax.device_get(x))
                          for x in src._pages_out(src_idx))
        blob = k_host.tobytes() + v_host.tobytes()
        half = len(blob) // 2
        k2 = np.frombuffer(blob[:half], dtype=k_host.dtype).reshape(
            k_host.shape)
        v2 = np.frombuffer(blob[half:], dtype=v_host.dtype).reshape(
            v_host.shape)
        dst._pages_in(dst_idx, k2, v2)
        _sync()

    def host_pipelined_once() -> None:
        # The round-5 chunked shuttle (worker._shuttle_send_chunked):
        # slice the block along L, start EVERY device→host copy async up
        # front, then stream chunks host→device as their bytes land — the
        # D2H of chunk i+1 overlaps the H2D of chunk i instead of the
        # two directions strictly alternating on one monolith.
        kd = dst.kv[0]
        kb, vb = src._pages_out(src_idx)
        L = int(kb.shape[0])
        C = max(2, min(L, 8))
        bounds = [(i * L // C, (i + 1) * L // C) for i in range(C)]
        parts = [(kb[lo:hi], vb[lo:hi]) for lo, hi in bounds if hi > lo]
        for pk, pv in parts:
            pk.copy_to_host_async()
            pv.copy_to_host_async()
        up = []
        for pk, pv in parts:
            k_host = np.asarray(pk)            # completes the async D2H
            v_host = np.asarray(pv)
            up.append((jnp.asarray(k_host).astype(kd.dtype),
                       jnp.asarray(v_host).astype(kd.dtype)))
        k2 = jnp.concatenate([u[0] for u in up], axis=0)
        v2 = jnp.concatenate([u[1] for u in up], axis=0)
        dst._pages_in(dst_idx, k2, v2)
        _sync()

    # Report the EFFECTIVE page count: callers print this next to the
    # bandwidth, and a silently clamped request must not claim a larger
    # measured block than was moved.
    out: Dict[str, float] = {"bytes": float(nbytes),
                             "pages": float(n_pages)}
    for name, fn in (("direct", direct_once), ("host", host_once),
                     ("host_pipelined", host_pipelined_once)):
        fn()                                   # warmup / compile
        t0 = time.monotonic()
        for _ in range(iters):
            fn()
        dt = (time.monotonic() - t0) / iters
        out[f"{name}_gbps"] = nbytes / dt / 1e9
    return out
