"""KV block export/inject: the worker-side half of disaggregated P/D.

Replaces the reference's NIXL RDMA block transfer (``lib/llm`` KVBM nixl
storage, ``nixl_connect`` SDK) with TPU-native paths:

- DCN/host path (this module): gather the named blocks from the device cache
  to host, ship them over the runtime's RPC plane, scatter them into the
  destination cache. Works across any two workers (different hosts, different
  pods) with no shared device fabric.
- ICI path (same-pod slices): when source and destination live in one jax
  process/mesh the blocks move as a device-to-device ``jax.device_put`` —
  same call surface, no host bounce.

Blocks are addressed by their chained content hash (``dynamo_tpu.tokens``),
so the destination commits them straight into its prefix cache and the
scheduler's normal prefix-match admission picks them up: "injection" is
indistinguishable from having computed the prefix locally.
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading
import time
import zlib
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dynamo_tpu.engine.jax_engine import JaxEngine
from dynamo_tpu.runtime.codec import Raw, byte_view

logger = logging.getLogger(__name__)

# kv_transfer_params keys (wire schema; parity in role with the reference's
# vLLM kv_transfer_params flow, components/backends/vllm/.../handlers.py)
#   blocks: [[block_hash, local_hash, parent_hash|0], ...]  (prefix order)
#   page_size, num_tokens_cached


@dataclass
class BlockPayload:
    """One transferred block: [L, 2, Hkv, page_size, Dh] of cache content."""

    block_hash: int
    local_hash: int
    parent_hash: Optional[int]
    data: np.ndarray

    def to_wire(self) -> Dict[str, Any]:
        # msgpack packs any buffer-protocol object as bin: a flat byte VIEW
        # of the block ships with no ``tobytes`` copy (non-contiguous or
        # extension-dtype data still pays one materializing copy inside
        # ``byte_view``)
        return {
            "block_hash": self.block_hash,
            "local_hash": self.local_hash,
            "parent_hash": self.parent_hash,
            "dtype": str(self.data.dtype),
            "shape": list(self.data.shape),
            "data": byte_view(self.data),
        }

    @classmethod
    def from_wire(cls, d: Dict[str, Any]) -> "BlockPayload":
        arr = np.frombuffer(d["data"], dtype=np.dtype(d["dtype"]))
        return cls(block_hash=d["block_hash"], local_hash=d["local_hash"],
                   parent_hash=d.get("parent_hash"),
                   data=arr.reshape(d["shape"]))


# Gather/scatter jits live on the ENGINE (``dispatch_gather_pages`` /
# ``scatter_pages_host`` / ``scatter_pages_device``, jax_engine.py) — one
# implementation serves the single-host, ICI, and multi-host-broadcast
# paths alike.


def export_blocks(engine: JaxEngine,
                  block_hashes: List[int]) -> List[BlockPayload]:
    """Extract resident blocks by hash as host payloads (the DCN/RPC path).
    Missing hashes break the chain (the destination recomputes the rest)."""
    metas, data = _export_device(engine, block_hashes)
    if not metas:
        return []
    host = np.asarray(jax.device_get(data))[:, :len(metas)]
    return [BlockPayload(block_hash=h, local_hash=local, parent_hash=parent,
                         data=host[:, i])
            for i, (h, local, parent) in enumerate(metas)]


def _inject_data(engine: JaxEngine,
                 metas: List[Tuple[int, int, Optional[int]]],
                 data, window: Optional[int] = None) -> int:
    """Core injection: ``metas[i] = (block_hash, local_hash, parent_hash)``
    describes page slice ``data[:, i]`` ([L, n, 2, Hkv, ps, Dh], host
    or device). Fresh blocks are scattered into the cache and registered;
    they land in the prefix-cache LRU, so the next admission of the matching
    prompt revives them. Returns blocks actually injected."""
    alloc = engine.allocator
    fresh = [i for i, m in enumerate(metas) if m[0] not in alloc._by_hash]
    if len(fresh) > alloc.num_free:
        # not worth evicting live cache for a partial chain; inject what fits
        fresh = fresh[:alloc.num_free]
    if not fresh:
        return 0
    pages = alloc.allocate(len(fresh))
    is_device = isinstance(data, jax.Array)
    if engine.step_tap is not None or not is_device:
        # host values (the wire path), and ALWAYS on multi-host: the
        # scatter is broadcast with its values so every rank applies the
        # identical write to the sharded cache
        host = np.asarray(data)
        if len(fresh) != len(metas):
            host = host[:, np.asarray(fresh, np.int64)]
        engine.scatter_pages_chunked(pages, host, window)
    else:
        # device values (same-process ICI path): no host bounce
        if len(fresh) != len(metas):
            data = data[:, jnp.asarray(fresh, jnp.int32)]
        engine.scatter_pages_device(pages, data)
    for page, i in zip(pages, fresh):
        h, local, parent = metas[i]
        alloc.commit(page, h, local, parent)
    alloc.release(pages)  # refcount 0 -> LRU, matchable by admission
    return len(fresh)


def inject_blocks(engine: JaxEngine, blocks: List[BlockPayload]) -> int:
    """Inject host-side block payloads (the DCN/RPC path)."""
    if not blocks:
        return 0
    metas = [(b.block_hash, b.local_hash, b.parent_hash) for b in blocks]
    data = np.stack([b.data for b in blocks], axis=1)  # [L,n,2,Hkv,ps,Dh]
    return _inject_data(engine, metas, data)


def _export_device(engine: JaxEngine, block_hashes: List[int],
                   sharded: bool = False):
    """Extract resident blocks by hash as (metas, device array) — no host
    round trip. Missing hashes break the chain (later blocks are useless
    without their parents). The gather goes through
    ``engine.dispatch_gather_pages`` so a multi-host engine broadcasts it
    to followers (every rank must join ops on the sharded cache).
    ``sharded=True`` keeps the gathered array on the cache's placement
    (no all-gather) for the per-shard export path."""
    alloc = engine.allocator
    claimed: List[Tuple[int, int]] = []
    try:
        for h in block_hashes:
            page = alloc._by_hash.get(h)
            if page is None:
                break
            alloc.incref(page)
            claimed.append((h, page))
        if not claimed:
            return [], None
        data = engine.dispatch_gather_pages([p for _h, p in claimed],
                                            replicate=not sharded)
        metas = []
        for h, page in claimed:
            info = alloc._info[page]
            metas.append((h, info.local_hash, info.parent_hash))
        return metas, data
    finally:
        alloc.release([p for _h, p in claimed])


def _put_like(vals, pages) -> "jax.Array":
    """Move a stacked [L, n, 2, Hkv, ps, Dh] array onto the sharding of the
    destination cache (device-to-device on a real mesh — ICI, not host)."""
    from dynamo_tpu.parallel.sharding import transport_sharding

    return jax.device_put(vals, transport_sharding(pages))


def cache_shard_layout(engine) -> Tuple[int, int]:
    """``(shard_count, axis)`` of the engine cache's stacked transport
    array ``[L, n, 2, Hkv, ps, Dh]``; ``(1, -1)`` for single-device or
    replicated caches (and on any error — shard negotiation is an
    optimization, never load-bearing)."""
    from dynamo_tpu.parallel.sharding import shard_layout, transport_sharding

    try:
        return shard_layout(transport_sharding(engine.pages))
    except Exception:  # noqa: BLE001 — fall back to merged frames
        logger.debug("cache shard layout probe failed", exc_info=True)
        return (1, -1)


def kv_shard_payload(engine) -> Dict[str, int]:
    """The shard-negotiation keys a puller merges into its wire-v5 pull
    payload: its own cache's shard layout, so an exporter with the SAME
    layout streams each shard's slice straight to its destination device.
    Empty for single-device caches and multi-host engines (per-shard
    frames carry no broadcast — followers could not join)."""
    n, ax = cache_shard_layout(engine)
    if n < 2 or engine.step_tap is not None:
        return {}
    return {"shards": n, "shard_axis": ax}


async def transfer_blocks_ici(src: JaxEngine, dst: JaxEngine,
                              block_hashes: List[int]) -> int:
    """Same-process prefill-to-decode block handoff: device-to-device via
    ``jax.device_put`` onto the destination cache's sharding (rides ICI on a
    TPU mesh), then a donated jitted scatter — the KV bytes never touch a
    ``np.ndarray``.

    This is the NIXL-replacement fast path (reference:
    ``lib/llm/src/block_manager/block/transfer/nixl.rs``,
    ``nixl_connect/__init__.py``); the RPC/DCN path (``BlockPayload`` over
    the runtime data plane) remains the cross-process fallback. Both legs
    run inside the owning engine's exclusive window, so neither races a
    pages-donating step.
    """
    metas, data = await src.run_exclusive(_export_device, src, block_hashes)
    if not metas:
        return 0

    def _inject(dst_engine, metas_, data_):
        moved = _put_like(data_[:, :len(metas_)], dst_engine.pages)
        return _inject_data(dst_engine, metas_, moved)

    return await dst.run_exclusive(_inject, dst, metas, data)


# blocks per wire frame on the batched export path: big enough that the
# per-frame overhead (one msgpack header + one drain) is noise against the
# raw bytes, small enough to pipeline — the receiver injects frame k while
# frame k+1 is still in flight. Default; ``kv_transfer_defaults`` resolves
# the configured value (DYN_KV_FRAME_BLOCKS / RuntimeConfig.kv_frame_blocks).
BLOCKS_PER_FRAME = 16

# max blocks committed per exclusive-window donated scatter on the inject
# side: larger windows amortize jit dispatch, smaller windows bound how
# long one KV commit can stall the decode loop between steps. Default;
# DYN_KV_SCATTER_BLOCKS / RuntimeConfig.kv_scatter_blocks override.
SCATTER_WINDOW_BLOCKS = 64

# wire schema: 1 = per-block msgpack dicts (``BlockPayload``), 2 = batched
# block-major two-part frames, 3 = batched LAYER-major frames (the staged
# inject path stages them with a straight strided copy — no per-frame
# transpose), 4 = v3 frames carrying a per-frame ``crc32`` of the raw
# bytes (the inject side verifies BEFORE staging — a truncated/corrupted
# frame is rejected, never silently injected as garbage KV), 5 = v4
# frames that may additionally be SHARD-SLICED: when the puller
# advertises a cache shard layout matching the exporter's
# (``{"shards": n, "shard_axis": a}`` in the pull payload), each block
# window ships as ``n`` frames each carrying ONE shard's slice of the
# transport array (``meta["shard"] = {index, count, axis, start, size}``)
# read straight off its source device — no all-gather, no full-size host
# buffer — and the inject side ``device_put``s each slice onto the
# destination shard's device. Pullers advertise the highest version they
# speak; exporters serve the min of that and their own, so mixed-version
# pulls keep working (a v3 puller just gets frames without the checksum
# key; a v4-or-below puller — or a v5 puller whose shard layout doesn't
# match — gets the merged host-gathered frames).
FRAME_WIRE_VERSION = 5


class FrameIntegrityError(ValueError):
    """A wire frame's bytes do not match its advertised crc32 — the frame
    was truncated or corrupted in transit and must not be injected."""


# TOML-layer cache for kv_transfer_defaults: with DYN_CONFIG_PATH set,
# RuntimeConfig.load() opens and parses the file — blocking IO that must
# not run per pull on the event loop. Keyed by (path, mtime) so edits
# still take effect; the env-only path (no config file) stays uncached
# (cheap, and tests monkeypatch env expecting fresh resolution).
_cfg_cache: Tuple[Any, Any] = (None, None)


def _runtime_cfg():
    global _cfg_cache
    from dynamo_tpu.utils.config import CONFIG_PATH_ENV, RuntimeConfig

    path = os.environ.get(CONFIG_PATH_ENV)
    if not path:
        return RuntimeConfig.load()  # env scan only — no file IO
    try:
        key = (path, os.stat(path).st_mtime_ns)
    except OSError:
        key = (path, None)
    cfg, ck = _cfg_cache
    if cfg is None or ck != key:
        cfg = RuntimeConfig.load()
        _cfg_cache = (cfg, key)
    return cfg


# Defaults layer (same shape as rpc.keepalive_defaults): RuntimeConfig
# (dataclass -> TOML -> DYN_RUNTIME_* env), then the short-form
# DYN_KV_FRAME_BLOCKS / DYN_KV_SCATTER_BLOCKS env wins. Resolved lazily —
# per pull/export, not at import — so monkeypatched env changes take
# effect and importing this module never does TOML file IO.
def kv_transfer_defaults() -> Tuple[int, int]:
    frame, window = BLOCKS_PER_FRAME, SCATTER_WINDOW_BLOCKS
    try:
        cfg = _runtime_cfg()
        frame, window = cfg.kv_frame_blocks, cfg.kv_scatter_blocks
    except Exception:  # a bad TOML/env must not break a KV pull
        logger.warning("bad runtime config; kv transfer falls back to "
                       "%d/%d blocks", frame, window, exc_info=True)
    raw_frame = os.environ.get("DYN_KV_FRAME_BLOCKS")
    raw_window = os.environ.get("DYN_KV_SCATTER_BLOCKS")
    try:
        frame = int(raw_frame) if raw_frame is not None else frame
    except (TypeError, ValueError):
        logger.warning("malformed DYN_KV_FRAME_BLOCKS %r; using %d",
                       raw_frame, frame)
    try:
        window = int(raw_window) if raw_window is not None else window
    except (TypeError, ValueError):
        logger.warning("malformed DYN_KV_SCATTER_BLOCKS %r; using %d",
                       raw_window, window)
    return max(1, frame), max(1, window)


def frame_crc_enabled() -> bool:
    """Per-frame crc32 on wire-v4 exports (``DYN_KV_FRAME_CRC=0``
    disables — the inject side simply sees no ``crc32`` key)."""
    return os.environ.get("DYN_KV_FRAME_CRC", "1") not in ("0", "false", "")


def resolve_wire(payload: Any, default_wire: int
                 ) -> Tuple[str, int, bool, Optional[Tuple[int, int]]]:
    """(frame layout, frame blocks, checksum, shards) for an export
    request's advertised wire version — the one place the version ->
    layout mapping lives, and resolved OUTSIDE the exclusive window
    (``kv_transfer_defaults`` can touch the config file). ``default_wire``
    encodes what a client that omits the key speaks: 1 on the RPC plane
    (per-block era), 2 on the bulk plane (which never carried the
    per-block schema). ``checksum`` is True when the puller speaks wire
    v4+ (and the exporter hasn't disabled crc). ``shards`` is the
    puller's advertised cache shard layout ``(count, axis)`` when it
    speaks wire v5+ and negotiated one, else None — ``export_frames``
    serves per-shard frames only when it matches the exporter's own
    layout (host-tier exports ignore it: their data is unsharded)."""
    payload = payload or {}
    wire = int(payload.get("wire", default_wire))
    layout = "layer" if wire >= 3 else "block"
    checksum = wire >= 4 and frame_crc_enabled()
    shards = None
    if wire >= 5:
        try:
            n = int(payload.get("shards", 0) or 0)
            if n >= 2:
                shards = (n, int(payload.get("shard_axis", -1)))
        except (TypeError, ValueError):
            shards = None
    return layout, kv_transfer_defaults()[0], checksum, shards


def export_frames(engine: JaxEngine, block_hashes: List[int],
                  layout: str = "layer",
                  frame_blocks: Optional[int] = None,
                  shards: Optional[Tuple[int, int]] = None) -> List[Raw]:
    """Extract resident blocks as batched two-part wire frames.

    ``layout="layer"`` (wire v3) keeps the device gather's layer-major
    ``[L, k, 2, Hkv, ps, Dh]`` order: the inject side stages each frame
    with one strided copy straight into its scatter buffer — no per-frame
    transpose on either end (each frame slice is materialized contiguous
    here; one copy pass total, same as v2's single moveaxis pass).
    ``layout="block"`` (wire v2 compat) transposes to block-major
    ``[k, L, ...]`` for pullers that predate the layer-major schema.
    Either way the raw bytes go from a numpy buffer to the socket with no
    msgpack/``tobytes`` re-copies (the role of the reference's NIXL
    descriptor-list transfers,
    ``lib/llm/src/block_manager/block/transfer/nixl.rs``).
    Runs under ``run_exclusive``.

    ``shards`` (wire v5, from ``resolve_wire``) is the puller's cache
    shard layout ``(count, axis)``: when it matches THIS engine's layout
    the gather skips the all-gather (the transport array keeps the cache
    placement) and each block window ships as ``count`` frames, one per
    shard slice read straight off its device — the host never
    materializes the merged array. A mismatched/unsupported layout logs
    the reason once per export and serves the merged single-frame-per-
    window schema every puller understands.
    """
    if shards is not None:
        mine = cache_shard_layout(engine)
        if (engine.step_tap is not None or layout != "layer"
                or mine != tuple(shards)):
            logger.info(
                "per-shard KV export unavailable (engine layout %s vs "
                "puller %s%s%s); serving merged frames", mine,
                tuple(shards),
                "; multihost" if engine.step_tap is not None else "",
                "; block-major" if layout != "layer" else "")
            shards = None
    if shards is not None:
        return _export_frames_sharded(engine, block_hashes, frame_blocks,
                                      mine)
    metas, data = _export_device(engine, block_hashes)
    if not metas:
        return []
    n = len(metas)
    # handlers resolve the knob OUTSIDE the exclusive window and pass it
    # in — kv_transfer_defaults can do TOML file IO, which must not stall
    # the decode loop behind this export
    per = int(frame_blocks) if frame_blocks else kv_transfer_defaults()[0]
    # host-side materialization: a device-side copy would be another jitted
    # op every mesh rank must join; one host memcpy is cheap next to the
    # wire time and keeps the multi-host path to exactly one broadcast op
    host = np.asarray(jax.device_get(data))[:, :n]
    if layout != "layer":
        host = np.ascontiguousarray(np.moveaxis(host, 1, 0))
    frames: List[Raw] = []
    for i in range(0, n, per):
        blocks = [[h, local, parent]
                  for h, local, parent in metas[i:i + per]]
        if layout == "layer":
            chunk = np.ascontiguousarray(host[:, i:i + per])
            meta = {"blocks": blocks, "dtype": str(chunk.dtype),
                    "block_shape": [chunk.shape[0]] + list(chunk.shape[2:]),
                    "layout": "layer"}
        else:
            chunk = host[i:i + per]
            meta = {"blocks": blocks, "dtype": str(chunk.dtype),
                    "block_shape": list(chunk.shape[1:])}
        frames.append(Raw(meta, chunk))
    # wire-v4 checksums are stamped by the serving handlers AFTERWARD via
    # ``stamp_frame_crcs`` — outside the exclusive window this runs under
    return frames


def _export_frames_sharded(engine: JaxEngine, block_hashes: List[int],
                           frame_blocks: Optional[int],
                           layout: Tuple[int, int]) -> List[Raw]:
    """Per-shard wire frames (wire v5): one frame per (block window,
    cache shard). The sharded gather keeps the transport array on the
    cache's placement, so each ``addressable_shards`` entry is that
    device's own slice — reading it is a device-local D2H copy of 1/n of
    the bytes, with no collective and no merged host buffer. Frames for
    one window are emitted consecutively (shard 0..n-1, identical
    ``blocks`` list) so the inject pipeline assembles them windowful by
    windowful. ``layout`` is the caller's already-negotiated
    (shard count, axis) — verified against the puller's advert. Runs
    under ``run_exclusive``."""
    metas, data = _export_device(engine, block_hashes, sharded=True)
    if not metas:
        return []
    n = len(metas)
    per = int(frame_blocks) if frame_blocks else kv_transfer_defaults()[0]
    count, axis = layout
    parts: List[Tuple[int, np.ndarray]] = []
    for sh in data.addressable_shards:
        if sh.replica_id != 0:
            continue  # axes the cache replicates over (e.g. sp) repeat
            # the same slice on several devices — ship each slice once
        start = sh.index[axis].start or 0
        parts.append((int(start), np.asarray(sh.data)[:, :n]))
    parts.sort(key=lambda p: p[0])
    if len(parts) != count:
        raise RuntimeError(
            f"sharded export found {len(parts)} distinct shard slices, "
            f"expected {count} — cache sharding changed mid-negotiation?")
    frames: List[Raw] = []
    for i in range(0, n, per):
        blocks = [[h, local, parent]
                  for h, local, parent in metas[i:i + per]]
        for si, (start, host) in enumerate(parts):
            chunk = np.ascontiguousarray(host[:, i:i + per])
            meta = {"blocks": blocks, "dtype": str(chunk.dtype),
                    "block_shape": [chunk.shape[0]] + list(chunk.shape[2:]),
                    "layout": "layer",
                    "shard": {"index": si, "count": count, "axis": axis,
                              "start": start,
                              "size": int(chunk.shape[axis])}}
            frames.append(Raw(meta, chunk))
    return frames


def stamp_frame_crcs(frames: List[Raw]) -> List[Raw]:
    """Stamp the wire-v4 per-frame crc32 onto already-exported frames.
    Serving handlers call this OUTSIDE the engine's exclusive window (the
    checksum is a per-byte pass over host memory — it must not stall the
    decode loop the way work inside ``run_exclusive`` would)."""
    for f in frames:
        f.obj["crc32"] = zlib.crc32(byte_view(f.raw)) & 0xFFFFFFFF
    return frames


def verify_frame(meta: Dict[str, Any], raw: Any) -> None:
    """Check a wire frame's bytes against its advertised ``crc32`` (wire
    v4); frames from older exporters carry no checksum and pass. Raises
    ``FrameIntegrityError`` on mismatch — the one gate between the wire
    and the cache, shared by every inject path via ``frame_arrays``."""
    want = meta.get("crc32")
    if want is None:
        return
    got = zlib.crc32(byte_view(raw)) & 0xFFFFFFFF
    if got != int(want):
        raise FrameIntegrityError(
            f"KV frame checksum mismatch: crc32 {got:#010x} != advertised "
            f"{int(want):#010x} over {len(memoryview(byte_view(raw)))} "
            f"bytes ({len(meta.get('blocks', []))} blocks) — frame "
            f"corrupted or truncated in transit")


def frame_arrays(meta: Dict[str, Any]
                 ) -> Tuple[List[Tuple[int, int, Optional[int]]],
                            np.ndarray]:
    """Decode one wire frame into ``(metas, values)`` where ``values`` is a
    layer-major ``[L, n, 2, Hkv, ps, Dh]`` ndarray VIEW aliasing
    ``meta["_raw"]`` — callers must copy (stage) before releasing the wire
    buffer. Handles both the v3 layer-major and v2 block-major layouts
    (``block_shape`` is the per-block ``[L, 2, Hkv, ps, Dh]`` in both).
    Wire-v4 frames are checksum-verified here — every inject path decodes
    through this function, so a corrupted frame can never reach the
    cache (raises ``FrameIntegrityError``)."""
    raw = meta["_raw"]
    verify_frame(meta, raw)
    bs = list(meta["block_shape"])
    n = len(meta["blocks"])
    arr = np.frombuffer(raw, dtype=np.dtype(meta["dtype"]))
    if meta.get("layout") == "layer":
        arr = arr.reshape([bs[0], n] + bs[1:])
    else:
        arr = np.moveaxis(arr.reshape([n] + bs), 0, 1)
    metas = [(b[0], b[1], b[2]) for b in meta["blocks"]]
    return metas, arr


def inject_frame(engine: JaxEngine, meta: Dict[str, Any]) -> int:
    """Inject one wire frame (either ``export_frames`` layout) directly.
    Runs under ``run_exclusive``. Returns blocks injected.

    The values are materialized as an OWNING copy: callers release the
    wire buffer back to the bulk freelist as soon as this returns, so
    nothing here may keep aliasing it (``jnp.asarray`` can zero-copy a
    contiguous numpy array on the CPU backend, and the device upload
    itself is async). The streaming pull path uses ``InjectPipeline``
    instead, which stages into a reusable buffer and batches the scatter.
    """
    if meta.get("shard") is not None:
        # a wire-v5 shard frame carries one slice of a block window —
        # standalone injection of partial data would commit garbage KV
        raise ValueError("per-shard wire frames require the inject "
                        "pipeline (InjectPipeline.add_frame)")
    metas, arr = frame_arrays(meta)
    return _inject_data(engine, metas, arr.copy())


def _commit_staged(engine: JaxEngine, metas, data, inner) -> int:
    """One batched commit inside the exclusive window. The caller refills
    the staging buffer the moment this resolves, so wait for the scatter
    to actually consume its values whenever they might still be read
    afterwards: host values (the multi-host step_tap path — ``jnp.asarray``
    starts an ASYNC H2D transfer from the reusable buffer), and any values
    on the CPU backend (``device_put``/``jnp.asarray`` may zero-copy ALIAS
    aligned host memory there). Only a device-resident upload on a real
    device backend keeps the window at the bare scatter dispatch."""
    n = inner(engine, metas, data)
    if (not isinstance(data, jax.Array)
            or jax.default_backend() == "cpu"):
        jax.block_until_ready(engine.pages)
    return n


class InjectPipeline:
    """Staged KV inject: recv -> stage -> upload -> commit.

    Wire frames (either schema) and legacy per-block payloads are STAGED
    into one of two preallocated layer-major host buffers; when a buffer
    reaches the scatter window it is UPLOADED onto the cache sharding
    (async ``jax.device_put``, outside any exclusive window — overlapping
    the socket) and COMMITTED with one batched donated scatter inside a
    minimal exclusive window. Double buffering lets frame k+1 stage while
    window k uploads/commits; the window knob (``DYN_KV_SCATTER_BLOCKS``)
    bounds how long any one commit can stall the decode loop, and decode
    steps run between windows.

    Callers may release each wire buffer as soon as ``add_frame`` returns
    (staging copies the bytes). Not thread-safe; drive from one task, then
    ``await finish()``. Per-phase wall time accumulates in ``timings``
    (``stage_s``/``upload_s``/``scatter_s``).

    On multi-host engines (``engine.step_tap`` set) the upload phase is
    skipped: the scatter must be broadcast WITH its host values so every
    rank applies the identical write — commits stay batched, host-side.
    """

    def __init__(self, engine: JaxEngine, window: Optional[int] = None,
                 commit: Optional[Callable] = None):
        self.engine = engine
        self.window = int(window) if window else kv_transfer_defaults()[1]
        self.injected = 0
        self.blocks_staged = 0
        self.timings: Dict[str, float] = {
            "stage_s": 0.0, "upload_s": 0.0, "scatter_s": 0.0}
        if commit is not None:
            self._inner = commit
        else:
            # pass the already-resolved window down so the host-path
            # chunked scatter never re-reads the config inside a commit
            self._inner = (lambda eng, metas, data:
                           _inject_data(eng, metas, data, self.window))
        self._bufs: List[Optional[np.ndarray]] = [None, None]
        self._cur = 0
        self._fill = 0
        self._metas: List[Tuple[int, int, Optional[int]]] = []
        self._pending: List[Optional[asyncio.Task]] = [None, None]
        self._direct: Optional[asyncio.Task] = None
        self._sharding = None
        # wire-v5 per-shard frames: parts of the block window currently
        # being assembled ({"key", "metas", "axis", "count", "parts"})
        # and the in-flight shard-window commit task
        self._shard_win: Optional[Dict[str, Any]] = None
        self._shard_task: Optional[asyncio.Task] = None
        # commit-order chain: uploads overlap freely, but windows COMMIT
        # in arrival order — under a near-full allocator, _inject_data
        # truncates to the free-page budget, and out-of-order commits
        # could keep a chain's tail while dropping its head (orphaned
        # children no admission chain-walk can ever match)
        self._commit_order: Optional[asyncio.Future] = None

    async def add_frame(self, meta: Dict[str, Any],
                        release: Optional[Callable] = None) -> None:
        """Stage one wire frame; commits whenever a window fills.

        Without ``release``, the bytes are copied out of ``meta["_raw"]``
        before this returns and the caller keeps ownership of the buffer.
        With ``release``, the pipeline OWNS the wire buffer and calls
        ``release(raw)`` once its bytes are consumed — which enables the
        ZERO-COPY frame path: a layer-major frame spanning at least one
        full scatter window uploads straight from the wire buffer (no
        staging pass) and the buffer is released only after the scatter
        has consumed the upload (``jax.device_put`` may alias an aligned
        host buffer on the CPU backend)."""
        try:
            metas, arr = frame_arrays(meta)
        except Exception:
            # ownership contract: even a malformed frame's buffer goes
            # back to the pool
            if release is not None:
                release(meta["_raw"])
            raise
        if meta.get("shard") is not None:
            try:
                await self._stage_shard(meta["shard"], metas, arr)
            finally:
                if release is not None:
                    release(meta["_raw"])
            return
        if (release is not None and self.engine.step_tap is None
                and meta.get("layout") == "layer"
                and len(metas) >= self.window and self._fill == 0):
            await self._direct_frame(metas, arr, meta["_raw"], release)
            return
        try:
            await self._stage(metas, arr)
        finally:
            if release is not None:
                release(meta["_raw"])

    async def add_blocks(self, blocks: List["BlockPayload"]) -> None:
        """Legacy per-block payloads ride the same staged/batched path."""
        for b in blocks:
            await self._stage(
                [(b.block_hash, b.local_hash, b.parent_hash)],
                b.data[:, None])

    async def finish(self) -> int:
        """Flush the partial window and wait out in-flight commits.
        Returns total blocks injected."""
        self._start_flush()
        tasks = [t for t in self._pending if t is not None]
        if self._direct is not None:
            tasks.append(self._direct)
        if self._shard_task is not None:
            tasks.append(self._shard_task)
        self._pending = [None, None]
        self._direct = None
        self._shard_task = None
        results = await asyncio.gather(*tasks, return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r
        if self._shard_win is not None:
            # the stream ended mid-window: some shard slices of the last
            # block window never arrived — treat as a transport fault so
            # the puller's resume ladder re-pulls the missing blocks
            # (committed windows stay, content-addressed)
            missing = (self._shard_win["count"]
                       - len(self._shard_win["parts"]))
            self._shard_win = None
            raise ConnectionError(
                f"sharded KV frame stream truncated: {missing} shard "
                "slice(s) of the final block window never arrived")
        return self.injected

    async def drain(self) -> int:
        """Best-effort ``finish`` for failure paths: waits out in-flight
        commits (so they neither leak tasks nor log unretrieved
        exceptions) without raising. Returns blocks injected so far —
        content-addressed blocks that landed from a broken stream are
        still good prefix."""
        try:
            return await self.finish()
        except Exception:  # noqa: BLE001 — the caller's branch already
            # failed; this must only reap
            logger.debug("staged KV commit failed during cleanup",
                         exc_info=True)
        return self.injected

    # -- internals ---------------------------------------------------------

    def _order_ticket(self) -> Tuple[Optional[asyncio.Future],
                                     asyncio.Future]:
        """(previous window's commit-done future, this window's) — taken
        synchronously at flush-start so task scheduling can't reorder."""
        prev = self._commit_order
        done: asyncio.Future = asyncio.get_running_loop().create_future()
        self._commit_order = done
        return prev, done

    async def _stage(self, metas, arr) -> None:
        pos, n = 0, len(metas)
        while pos < n:
            if self._fill >= self.window:
                await self._rotate()
            buf = self._ensure_buf(arr)
            take = min(n - pos, self.window - self._fill)
            t0 = time.perf_counter()
            buf[:, self._fill:self._fill + take] = arr[:, pos:pos + take]
            self.timings["stage_s"] += time.perf_counter() - t0
            self._metas.extend(metas[pos:pos + take])
            self._fill += take
            self.blocks_staged += take
            pos += take
        if self._fill >= self.window:
            # flush eagerly: the upload overlaps the NEXT frame's recv
            await self._rotate()

    def _ensure_buf(self, arr) -> np.ndarray:
        shape = (arr.shape[0], self.window) + arr.shape[2:]
        buf = self._bufs[self._cur]
        if buf is None or buf.shape != shape or buf.dtype != arr.dtype:
            if self._fill:
                raise ValueError("frame geometry changed mid-window: "
                                 f"{buf.shape}/{buf.dtype} vs "
                                 f"{shape}/{arr.dtype}")
            buf = np.empty(shape, arr.dtype)
            self._bufs[self._cur] = buf
        return buf

    async def _rotate(self) -> None:
        self._start_flush()
        self._cur ^= 1
        # double buffer: the slot being switched into must have finished
        # its upload+commit before its bytes are overwritten (this await
        # is also the backpressure on the recv side)
        prev = self._pending[self._cur]
        if prev is not None:
            self._pending[self._cur] = None
            await prev

    def _start_flush(self) -> None:
        if not self._fill:
            return
        idx = self._cur
        buf, metas, fill = self._bufs[idx], self._metas, self._fill
        self._metas, self._fill = [], 0
        prev, done = self._order_ticket()
        self._pending[idx] = asyncio.create_task(
            self._flush(buf, metas, fill, prev, done))

    async def _upload(self, vals):
        t0 = time.perf_counter()
        dev = jax.device_put(vals, await self._target_sharding())
        # wait for the async transfer OUTSIDE any exclusive window: the
        # commit must be the bare scatter dispatch (skip the thread hop
        # when the backend finished synchronously)
        if not dev.is_ready():
            await asyncio.to_thread(jax.block_until_ready, dev)
        self.timings["upload_s"] += time.perf_counter() - t0
        return dev

    async def _commit_vals(self, metas, vals) -> None:
        t0 = time.perf_counter()
        # assign AFTER the await: ``self.injected += await ...`` loads the
        # attribute before suspending, so two in-flight flushes would lose
        # one commit's count
        n = await self.engine.run_exclusive(
            _commit_staged, self.engine, metas, vals, self._inner)
        self.injected += n
        self.timings["scatter_s"] += time.perf_counter() - t0

    async def _flush(self, buf, metas, fill, prev, done) -> None:
        try:
            vals: Any = buf[:, :fill]
            if self.engine.step_tap is None:
                vals = await self._upload(vals)
            if prev is not None:
                # uploads overlap; COMMITS go in window order (the chain
                # future resolves even when the prior commit failed — a
                # broken head already orphans the tail either way)
                await prev
            await self._commit_vals(metas, vals)
        finally:
            if not done.done():
                done.set_result(None)

    async def _direct_frame(self, metas, arr, raw, release) -> None:
        """Zero-copy frame path: upload the whole layer-major frame
        straight from the wire buffer (async — the transfer overlaps the
        next frame's recv AND the previous frame's scatter), then commit
        it in window-bounded scatters from a background task; the buffer
        is released only after the last commit has consumed the upload."""
        try:
            t0 = time.perf_counter()
            dev = jax.device_put(arr, await self._target_sharding())
            self.timings["upload_s"] += time.perf_counter() - t0
        except BaseException:
            # ownership contract: a failure before the commit task exists
            # must still return the wire buffer (once the task is created,
            # its finally owns the release)
            release(raw)
            raise
        self.blocks_staged += len(metas)
        order_prev, order_done = self._order_ticket()

        async def commit():
            try:
                if not dev.is_ready():
                    # the wait happens HERE, off the recv path, so frame
                    # k+1's upload dispatches while k's is still copying;
                    # the exclusive window still sees a ready buffer
                    t1 = time.perf_counter()
                    await asyncio.to_thread(jax.block_until_ready, dev)
                    self.timings["upload_s"] += time.perf_counter() - t1
                if order_prev is not None:
                    await order_prev  # commit in window order
                if len(metas) <= self.window:
                    await self._commit_vals(metas, dev)
                    return
                for i in range(0, len(metas), self.window):
                    chunk = metas[i:i + self.window]
                    await self._commit_vals(chunk,
                                            dev[:, i:i + len(chunk)])
            finally:
                if not order_done.done():
                    order_done.set_result(None)
                release(raw)

        prev, self._direct = self._direct, asyncio.create_task(commit())
        if prev is not None:  # bound in-flight commits (backpressure)
            await prev

    async def _stage_shard(self, shard: Dict[str, Any], metas, arr) -> None:
        """Wire-v5 per-shard frame path: accumulate the ``count`` shard
        slices of one block window, then assemble them into ONE sharded
        device array (``jax.make_array_from_single_device_arrays`` — each
        slice lands on exactly the destination device(s) holding it, no
        merged host buffer, no resharding) and commit it through the
        device-values scatter. The exporter emits a window's shard frames
        consecutively with identical ``blocks`` lists."""
        count, axis = int(shard["count"]), int(shard["axis"])
        sharding = await self._target_sharding()
        from dynamo_tpu.parallel.sharding import shard_layout
        if shard_layout(sharding) != (count, axis):
            # negotiation happened against a different engine/layout —
            # committing a mis-sliced window would be silent KV corruption
            raise ValueError(
                f"shard frame layout ({count}, {axis}) does not match the "
                f"destination cache {shard_layout(sharding)}")
        key = tuple(m[0] for m in metas)
        win = self._shard_win
        if win is None:
            win = self._shard_win = {"key": key, "metas": list(metas),
                                     "axis": axis, "count": count,
                                     "parts": {}}
        elif win["key"] != key or win["count"] != count:
            raise ValueError("sharded frame stream interleaved two block "
                             "windows; expected consecutive shard slices")
        start = int(shard["start"])
        if start in win["parts"]:
            raise ValueError(f"duplicate shard slice at offset {start}")
        t0 = time.perf_counter()
        # OWNING copy off the wire buffer: the view aliases meta["_raw"],
        # which the caller releases (back to the bulk/RPC buffer pool) the
        # moment we return, while this part waits for the window's other
        # slices. Must be .copy() — ascontiguousarray would alias the
        # already-contiguous view and the pool's next same-sized frame
        # would overwrite the staged bytes before commit.
        win["parts"][start] = arr.copy()
        self.timings["stage_s"] += time.perf_counter() - t0
        if len(win["parts"]) < count:
            return
        self._shard_win = None
        self.blocks_staged += len(win["metas"])
        await self._commit_shard_window(win, sharding)

    async def _commit_shard_window(self, win: Dict[str, Any],
                                   sharding) -> None:
        """Upload each assembled shard slice onto its destination
        device(s) and commit the window; ordered with every other window
        via the commit chain, overlapped with the next window's recv via
        a background task (the ``_direct_frame`` pattern)."""
        axis, parts = win["axis"], win["parts"]
        first = next(iter(parts.values()))
        gshape = list(first.shape)
        gshape[axis] = sum(p.shape[axis] for p in parts.values())
        gshape = tuple(gshape)
        t0 = time.perf_counter()
        arrays = []
        # one device_put per destination device; an axis the cache
        # REPLICATES over (e.g. sp) maps several devices to one slice —
        # each replica gets its own copy
        for dev, idx in sharding.devices_indices_map(gshape).items():
            start = idx[axis].start or 0
            stop = idx[axis].stop if idx[axis].stop is not None \
                else gshape[axis]
            part = parts.get(int(start))
            if part is None or part.shape[axis] != stop - start:
                raise ValueError(
                    f"shard slice [{start}:{stop}) missing or mis-sized "
                    "for the destination placement")
            arrays.append(jax.device_put(part, dev))
        vals = jax.make_array_from_single_device_arrays(
            gshape, sharding, arrays)
        self.timings["upload_s"] += time.perf_counter() - t0
        order_prev, order_done = self._order_ticket()
        metas = win["metas"]

        async def commit():
            try:
                if not vals.is_ready():
                    t1 = time.perf_counter()
                    await asyncio.to_thread(jax.block_until_ready, vals)
                    self.timings["upload_s"] += time.perf_counter() - t1
                if order_prev is not None:
                    await order_prev  # commit in window order
                if len(metas) <= self.window:
                    await self._commit_vals(metas, vals)
                    return
                for i in range(0, len(metas), self.window):
                    chunk = metas[i:i + self.window]
                    await self._commit_vals(chunk,
                                            vals[:, i:i + len(chunk)])
            finally:
                if not order_done.done():
                    order_done.set_result(None)

        prev, self._shard_task = (self._shard_task,
                                  asyncio.create_task(commit()))
        if prev is not None:  # bound in-flight commits (backpressure)
            await prev

    async def _target_sharding(self):
        if self._sharding is None:
            # pages is donated through every step: read its sharding inside
            # an exclusive window once, reuse for every upload
            def grab(engine):
                from dynamo_tpu.parallel.sharding import transport_sharding

                return transport_sharding(engine.pages)
            self._sharding = await self.engine.run_exclusive(grab,
                                                             self.engine)
        return self._sharding


async def inject_device_windowed(engine: JaxEngine,
                                 metas: List[Tuple[int, int, Optional[int]]],
                                 data, window: Optional[int] = None) -> int:
    """Commit an already-on-device value array in windows of at most
    ``window`` blocks, one minimal exclusive scatter each — decode steps
    interleave between windows instead of stalling behind one giant
    scatter (the device-direct plane's batched inject)."""
    window = int(window) if window else kv_transfer_defaults()[1]
    injected = 0
    for i in range(0, len(metas), window):
        chunk = metas[i:i + window]
        injected += await engine.run_exclusive(
            _inject_data, engine, chunk, data[:, i:i + len(chunk)],
            window)
    return injected


async def pump_bulk_frames(pipe: InjectPipeline, address: str,
                           endpoint: str, payload: Any, ident: str = "",
                           timeout: float = 60.0,
                           on_meta: Optional[Callable] = None,
                           inflight: int = 4) -> float:
    """Drive one bulk fetch's frames into an inject pipeline from the
    event loop: frames hop from the fetch thread through a bounded queue
    (backpressure: at most ``inflight`` un-staged frames — a slow
    injector must not buffer the whole prefix in RAM) and stage/commit
    while later frames are still on the wire. Wire buffers are owned by
    the pipeline (released right after staging, or post-commit on the
    zero-copy path). ``on_meta(meta, nbytes)`` runs per frame before
    staging (byte accounting). Returns seconds spent waiting on the
    socket/queue; raises on transport/handler/commit error AFTER reaping
    the fetch thread, the queue get, and in-flight commits — the caller
    reads ``pipe.injected`` for what landed, then calls ``pipe.finish()``
    itself on success."""
    import threading

    from dynamo_tpu.runtime.bulk import bulk_fetch
    from dynamo_tpu.runtime.codec import release_buffer

    loop = asyncio.get_running_loop()
    frame_q: asyncio.Queue = asyncio.Queue()
    abort = threading.Event()
    window = threading.Semaphore(inflight)
    recv_s = 0.0

    def on_frame(meta, raw):
        while not window.acquire(timeout=0.5):
            if abort.is_set():
                raise ConnectionError("bulk fetch aborted")
        loop.call_soon_threadsafe(frame_q.put_nowait, (meta, raw))

    async def stage_one(meta, raw):
        meta = dict(meta)
        meta["_raw"] = raw
        try:
            try:
                if on_meta is not None:
                    on_meta(meta, len(raw))
            except BaseException:
                release_buffer(raw)  # add_frame never took ownership
                raise
            await pipe.add_frame(meta, release=release_buffer)
        finally:
            window.release()

    fetch = asyncio.create_task(asyncio.to_thread(
        bulk_fetch, address, endpoint, payload, ident, timeout, on_frame,
        abort))
    get = None
    try:
        while True:
            get = asyncio.ensure_future(frame_q.get())
            t0 = time.perf_counter()
            done, _ = await asyncio.wait(
                {get, fetch}, return_when=asyncio.FIRST_COMPLETED)
            recv_s += time.perf_counter() - t0
            if get in done:
                meta, raw = get.result()
                await stage_one(meta, raw)
                continue
            get.cancel()
            await fetch  # raises on transport/handler error
            while not frame_q.empty():  # drain the tail
                meta, raw = frame_q.get_nowait()
                await stage_one(meta, raw)
            return recv_s
    except BaseException:
        # reap BEFORE propagating — including on task CancelledError
        # (client disconnect): a to_thread task only completes when its
        # thread exits, and the thread exits via the abort check; the
        # queue get and in-flight commits must not spill unretrieved
        # exceptions into the caller
        abort.set()
        if get is not None:
            get.cancel()
        if not fetch.done():
            fetch.cancel()
        try:
            await fetch
        except (Exception, asyncio.CancelledError):  # noqa: BLE001
            pass
        while not frame_q.empty():  # un-staged frames: pool their buffers
            _m, raw = frame_q.get_nowait()
            release_buffer(raw)
        await pipe.drain()
        raise
    finally:
        abort.set()


def serve_kv_export_bulk(engine: JaxEngine, loop):
    """Bulk-plane handler (``runtime/bulk.py``): synchronous, runs in the
    bulk connection's thread, coordinates with the engine loop via
    ``run_coroutine_threadsafe`` so the gather still happens inside an
    exclusive window. Yields (meta, buffer) pairs in the same schema as
    ``export_frames``."""

    def handler(payload):
        payload = payload or {}
        hashes = list(payload.get("block_hashes", []))
        # clients that predate wire v3 omit the key and get the block-major
        # v2 frames they expect (mixed-version pulls keep working)
        layout, per, crc, shards = resolve_wire(payload, 2)
        fut = asyncio.run_coroutine_threadsafe(
            engine.run_exclusive(export_frames, engine, hashes, layout,
                                 per, shards),
            loop)
        frames = fut.result(timeout=120.0)
        if crc:  # checksummed in THIS (bulk connection) thread — never
            # inside the exclusive window, never on the event loop
            stamp_frame_crcs(frames)
        for f in frames:
            yield f.obj, f.raw

    return handler


def serve_kv_export(engine: JaxEngine):
    """RPC handler factory: serves block fetches for disagg decode workers.

    Endpoint payload: {"block_hashes": [...], "wire": N}; clients that
    advertise ``wire >= 3`` get layer-major two-part frames, ``wire == 2``
    gets the block-major v2 frames, and older clients (whose codec would
    reject the raw-trailer length bit) get the per-block msgpack schema.
    The export runs via ``run_exclusive`` so it never races a
    pages-donating engine step.
    """

    async def handler(payload: Any, ctx):
        payload = payload or {}
        if payload.get("ack_lease") is not None:
            # puller committed (or abandoned) its pull: release the export
            # lease so the pinned pages go back to the LRU now instead of
            # waiting out the TTL
            ok = await release_export_lease(engine,
                                            int(payload["ack_lease"]))
            yield {"acked": bool(ok)}
            return
        hashes = list(payload.get("block_hashes", []))
        wire = int(payload.get("wire", 1))
        if wire >= 2:
            layout, per, crc, shards = resolve_wire(payload, 1)
            frames = await engine.run_exclusive(export_frames, engine,
                                                hashes, layout, per,
                                                shards)
            if crc:  # outside the exclusive window
                stamp_frame_crcs(frames)
            for f in frames:
                yield f
        else:
            blocks = await engine.run_exclusive(export_blocks, engine,
                                                hashes)
            for b in blocks:
                yield b.to_wire()

    return handler


# ---------------------------------------------------------------------------
# Export leases: TTL-bounded pinning of advertised KV blocks
# ---------------------------------------------------------------------------

# default lease lifetime; env DYN_KV_EXPORT_TTL_S overrides per grant
EXPORT_TTL_S = 120.0


def export_ttl_s() -> float:
    raw = os.environ.get("DYN_KV_EXPORT_TTL_S")
    if raw is None:
        return EXPORT_TTL_S
    try:
        return max(0.1, float(raw))
    except (TypeError, ValueError):
        logger.warning("malformed DYN_KV_EXPORT_TTL_S %r; using %.0f",
                       raw, EXPORT_TTL_S)
        return EXPORT_TTL_S


class ExportLeaseManager:
    """TTL'd pins on KV pages a prefill worker has advertised for pull.

    Without leases the handoff window is fragile both ways: the advertised
    blocks sit refcount-0 in the LRU and can be EVICTED before the decode
    side pulls them (wasting the remote prefill), while naive permanent
    pinning would let a decode worker that crashes after prefill strand
    pages forever. A lease pins the pages (``PageAllocator.claim_blocks``)
    until the puller acks (``{"ack_lease": id}`` on the kv_export
    endpoint) or the TTL (``DYN_KV_EXPORT_TTL_S``) expires and a GC sweep
    reclaims them — so orphaned KV from crashed decoders is bounded AND
    observable (``dynamo_worker_kv_exports_active`` /
    ``_reclaimed_total``).

    Allocator mutations run under ``run_exclusive`` (grant/release/sweep
    are host-metadata-only but the allocator is also touched from
    exclusive worker threads); sweeps are armed per grant with
    ``loop.call_later`` — no long-lived GC task to leak across engine
    lifetimes. Pinned pages are capped at half the allocator so a flood
    of un-acked exports can never starve prefill admission."""

    def __init__(self, engine: JaxEngine):
        self._engine = engine
        # lease_id -> (deadline, pages, kind); kind "export" = a disagg
        # pull's advertised prefix, "prefetch" = tier blocks the KVBM
        # prefetch scheduler promoted ahead of a request's prefill cursor
        # (kvbm/prefetch.py) — same pin primitive, same half-allocator
        # hard cap, separate observability
        self._leases: Dict[int, Tuple[float, List[int], str]] = {}
        self._next_id = 1
        self._lock = threading.Lock()
        self._sweep_tasks: set = set()
        self.granted_total = 0
        self.reclaimed_total = 0
        self.max_pinned_pages = max(1,
                                    (engine.allocator.num_pages - 1) // 2)

    # -- observers ---------------------------------------------------------

    @property
    def active(self) -> int:
        with self._lock:
            return len(self._leases)

    @property
    def pinned_pages(self) -> int:
        with self._lock:
            return sum(len(p) for _dl, p, _k in self._leases.values())

    def active_kind(self, kind: str) -> int:
        with self._lock:
            return sum(1 for _dl, _p, k in self._leases.values()
                       if k == kind)

    def pinned_pages_kind(self, kind: str) -> int:
        with self._lock:
            return sum(len(p) for _dl, p, k in self._leases.values()
                       if k == kind)

    def holds(self, lease_id: int) -> bool:
        """Whether a lease is still live (not released, not TTL-swept)."""
        with self._lock:
            return lease_id in self._leases

    def _gauge(self) -> None:
        try:
            from dynamo_tpu.worker.metrics import get_worker_metrics
            get_worker_metrics().kv_exports_active.set(
                self.active_kind("export"))
        except Exception:  # noqa: BLE001 — metrics must not fail the grant
            pass

    # -- allocator-side halves (run under run_exclusive) -------------------

    def grant_sync(self, hashes: List[int], ttl: Optional[float] = None,
                   kind: str = "export") -> Tuple[Optional[int], int]:
        """Synchronous grant for callers ALREADY inside an exclusive
        window (e.g. an ``InjectPipeline`` commit callback pinning blocks
        in the same window that committed them, so eviction pressure can
        never snatch a block between commit and pin). Returns
        ``(lease_id, pages_pinned)``; the caller must ``arm_sweep(ttl)``
        from the event loop afterwards (a later-armed timer still fires
        past the deadline, and every sweep reclaims ALL expired leases)."""
        ttl = export_ttl_s() if ttl is None else float(ttl)
        self._sweep_sync()  # reclaim expired pins before the cap check
        alloc = self._engine.allocator
        with self._lock:
            pinned = sum(len(p) for _dl, p, _k in self._leases.values())
            budget = self.max_pinned_pages - pinned
            if budget <= 0:
                if kind == "export":
                    logger.warning(
                        "export lease refused: %d pages already pinned "
                        "(cap %d) — decode pulls failing or not acking?",
                        pinned, self.max_pinned_pages)
                else:
                    # a long prompt hitting the cap is NORMAL for prefetch
                    # pins (the overflow stays ordinary LRU); not a fault
                    logger.debug(
                        "%s lease refused: %d pages pinned (cap %d)",
                        kind, pinned, self.max_pinned_pages)
                return None, 0
            pages = alloc.claim_blocks(hashes)
            if len(pages) > budget:
                # the cap is a hard bound, not a pre-check: trim the claim
                # so ONE big grant can never push pinned pages past it and
                # starve prefill admission — a head-of-chain pin is still
                # worth having (the tail stays ordinary LRU)
                alloc.release(pages[budget:])
                pages = pages[:budget]
            if not pages:
                return None, 0
            lease_id = self._next_id
            self._next_id += 1
            self._leases[lease_id] = (time.monotonic() + ttl, pages, kind)
            self.granted_total += 1
            n = len(pages)
        self._gauge()
        return lease_id, n

    def _grant_sync(self, hashes: List[int], ttl: float,
                    kind: str = "export") -> Optional[int]:
        return self.grant_sync(hashes, ttl, kind)[0]

    def _release_sync(self, lease_id: int) -> bool:
        with self._lock:
            ent = self._leases.pop(lease_id, None)
        if ent is None:
            return False
        self._engine.allocator.release(ent[1])
        self._gauge()
        return True

    def _sweep_sync(self) -> int:
        now = time.monotonic()
        with self._lock:
            expired = [(i, self._leases[i])
                       for i, (dl, _p, _k) in list(self._leases.items())
                       if dl <= now]
            for i, _e in expired:
                del self._leases[i]
            self.reclaimed_total += len(expired)
        for _i, (_dl, pages, _k) in expired:
            self._engine.allocator.release(pages)
        if expired:
            logger.warning("reclaimed %d orphaned KV lease(s) "
                           "(%d pages) past TTL", len(expired),
                           sum(len(p) for _i, (_d, p, _k) in expired))
            self._gauge()
            try:
                from dynamo_tpu.worker.metrics import get_worker_metrics
                get_worker_metrics().kv_exports_reclaimed.inc(len(expired))
            except Exception:  # noqa: BLE001
                pass
        return len(expired)

    # -- async surface (event loop) ----------------------------------------

    async def grant(self, hashes: List[int],
                    ttl: Optional[float] = None,
                    kind: str = "export") -> Optional[int]:
        """Pin the resident chain of ``hashes`` for one pull; returns the
        lease id (wire-safe) or None when nothing is resident / the pin
        cap is hit (the export still works, it just isn't protected)."""
        ttl = export_ttl_s() if ttl is None else float(ttl)
        lease = await self._engine.run_exclusive(self._grant_sync,
                                                 list(hashes), ttl, kind)
        if lease is not None:
            self.arm_sweep(ttl)
        return lease

    async def release(self, lease_id: int) -> bool:
        return await self._engine.run_exclusive(self._release_sync,
                                                int(lease_id))

    def release_detached(self, lease_id: int) -> bool:
        """Release without touching the engine loop: for teardown paths
        where the loop is stopped/dead (``run_exclusive`` would restart
        it). Safe there because nothing races the allocator anymore."""
        try:
            return self._release_sync(int(lease_id))
        except Exception:  # noqa: BLE001 — TTL covers a failed release
            logger.debug("detached lease release failed", exc_info=True)
            return False

    def arm_sweep(self, ttl: float) -> None:
        # one timer per grant, firing just past that lease's deadline: a
        # sweep reclaims EVERY expired lease, and a dropped timer (loop
        # closed) costs nothing — no persistent GC task to leak
        loop = asyncio.get_running_loop()
        loop.call_later(ttl + 0.02, self._sweep_soon, loop)

    def _sweep_soon(self, loop) -> None:
        with self._lock:
            if not self._leases:
                return
        task = loop.create_task(self._sweep_async())
        self._sweep_tasks.add(task)
        task.add_done_callback(self._sweep_tasks.discard)

    async def _sweep_async(self) -> None:
        eng = self._engine
        try:
            if (getattr(eng, "_stopping", False)
                    or eng._loop_task is None or eng._loop_task.done()):
                # engine loop is gone: nothing races the allocator anymore
                # (and run_exclusive would restart the loop) — sweep inline
                self._sweep_sync()
            else:
                await eng.run_exclusive(self._sweep_sync)
        except Exception:  # noqa: BLE001 — GC is best-effort
            logger.debug("export lease sweep failed", exc_info=True)


def _lease_engine(engine) -> Optional[JaxEngine]:
    """The JaxEngine whose allocator holds the advertised blocks, or None
    when ``engine`` has no page allocator (Echo/Mocker engines, disagg
    handlers). Unwraps one wrapper layer (``TieredEngine.engine``)."""
    for cand in (engine, getattr(engine, "engine", None)):
        if (cand is not None and hasattr(cand, "allocator")
                and hasattr(cand, "run_exclusive")):
            return cand
    return None


def get_export_leases(engine) -> Optional[ExportLeaseManager]:
    """The per-engine lease manager (created on first use), or None when
    the engine cannot pin pages."""
    eng = _lease_engine(engine)
    if eng is None:
        return None
    mgr = getattr(eng, "_export_leases", None)
    if mgr is None:
        mgr = ExportLeaseManager(eng)
        eng._export_leases = mgr
    return mgr


async def grant_export_lease(engine, hashes: List[int],
                             ttl: Optional[float] = None) -> Optional[int]:
    """Pin ``hashes`` on ``engine`` under a TTL'd export lease; returns
    the lease id for the puller to ack, or None (no-op engines, nothing
    resident, pin cap). Never raises — an unprotected export beats a
    failed prefill."""
    mgr = get_export_leases(engine)
    if mgr is None or not hashes:
        return None
    try:
        return await mgr.grant(hashes, ttl)
    except Exception:  # noqa: BLE001 — lease is protection, not a gate
        logger.exception("export lease grant failed")
        return None


async def stamp_export_lease(engine, params: Optional[Dict[str, Any]],
                             span=None) -> Optional[int]:
    """Grant an export lease for ``params["blocks"]`` and stamp the id
    into ``params["lease"]`` (+ a ``kv_export_lease`` span attr) — the
    one protocol shared by every export-advertising site (direct prefill
    handler, queue worker, prefill-first forward)."""
    blocks = (params or {}).get("blocks")
    if not blocks:
        return None
    lease = await grant_export_lease(engine, [b[0] for b in blocks])
    if lease is not None:
        params["lease"] = lease
        if span is not None:
            span.set_attr("kv_export_lease", lease)
    return lease


async def release_export_lease(engine, lease_id: int) -> bool:
    """Ack one export lease (puller-side commit/abandon signal)."""
    eng = _lease_engine(engine)
    mgr = getattr(eng, "_export_leases", None) if eng is not None else None
    if mgr is None:
        return False
    try:
        return await mgr.release(lease_id)
    except Exception:  # noqa: BLE001 — TTL covers a failed release
        logger.debug("export lease release failed", exc_info=True)
        return False


# ---------------------------------------------------------------------------
# Device-direct cross-process transfer (jax.experimental.transfer)
# ---------------------------------------------------------------------------

# offered device arrays are dropped if nobody pulled them in this window
OFFER_TTL_S = 120.0


class DeviceTransferPlane:
    """Cross-process device-to-device KV block pulls — the NIXL RDMA role
    proper (reference ``lib/llm/src/block_manager/block/transfer/nixl.rs``,
    ``nixl_connect/__init__.py:975-1122``).

    Built on ``jax.experimental.transfer``: the prefill worker OFFERS a
    gathered device array under a uuid on its transfer server; the decode
    worker PULLS it straight into its own jax client — on TPU the bytes
    ride the accelerator-aware transports, never a numpy host bounce
    (contrast: the bulk/RPC planes gather to host, ship sockets, scatter
    back). The offer/pull rendezvous metadata (uuid, address, shape,
    dtype, block hashes) travels over the ordinary RPC control plane
    (``serve_kv_export`` with ``{"direct": true}``).

    Scope: single-device-per-process engines (the common prefill/decode
    pair). Engines sharded over a mesh keep the bulk/RPC planes — a pull
    onto a NamedSharding needs a shared global mesh across processes.
    """

    # bound the per-address connection cache: prefill restarts advertise
    # fresh ephemeral ports, so a long-lived decode worker would otherwise
    # accumulate one dead connection per historical address
    MAX_CONNS = 8
    # bound on offers awaiting a pull/ack. jaxlib's transfer server keeps
    # an offered array registered until pulled (there is no retract API),
    # so a decode side that keeps failing its pulls would otherwise pin
    # one gathered array per request in HBM forever — past the cap,
    # offer() refuses (returns None) and the decode falls down the
    # transport ladder while still being served.
    MAX_OUTSTANDING_OFFERS = 32

    def __init__(self, host: str = "127.0.0.1"):
        import threading as _threading

        self.host = host
        self._server = None
        self._conns: Dict[str, Any] = {}
        self._offers: Dict[int, Tuple[float, Any]] = {}
        self._next_uuid = int(time.time() * 1000) % (1 << 40)
        # offers mutate from the engine's exclusive worker thread AND the
        # ack handler on the event loop; conns from concurrent pull threads
        self._lock = _threading.Lock()
        # server startup gets its OWN lock: start_transfer_server dials
        # transports and can hang on a wedged backend — evict()/ack() on
        # the event loop must never wait behind it
        self._init_lock = _threading.Lock()

    # -- common ------------------------------------------------------------

    def _ensure_server(self):
        if self._server is not None:  # fast path, no lock
            return self._server
        with self._init_lock:  # concurrent first pulls must not double-init
            if self._server is None:
                import jax as _jax
                from jax.experimental import transfer as _transfer

                client = _jax.devices()[0].client
                # explicit transport addresses: without them the cross-
                # process bulk-transport factory CHECK-fails (jaxlib
                # streaming.cc:193)
                self._server = _transfer.start_transfer_server(
                    client, f"{self.host}:0", [f"{self.host}:0"])
            return self._server

    @property
    def address(self) -> str:
        addr = self._ensure_server().address()
        # jaxlib may report a wildcard bind; rewrite to the serve host
        if addr.startswith(("0.0.0.0:", "[::]:")):
            addr = f"{self.host}:{addr.rsplit(':', 1)[1]}"
        return addr

    # -- source (prefill) side ---------------------------------------------

    def _prune_offers_locked(self, now: float) -> None:
        self._offers = {u: (t, a) for u, (t, a) in self._offers.items()
                        if now - t < OFFER_TTL_S}

    def offer_array(self, data) -> Dict[str, Any]:
        """Register one device array for a single pull and return the
        rendezvous dict (no ``blocks`` metadata — callers add their own).
        Raises RuntimeError past ``MAX_OUTSTANDING_OFFERS``."""
        now = time.time()
        server = self._ensure_server()
        with self._lock:
            self._prune_offers_locked(now)
            if len(self._offers) >= self.MAX_OUTSTANDING_OFFERS:
                raise RuntimeError(
                    f"{len(self._offers)} un-acked offers outstanding — "
                    f"refusing to pin more HBM (decode pulls failing?)")
            uuid = self._next_uuid
            self._next_uuid += 1
            # reserve the slot + keep the array referenced until acked or
            # TTL; jaxlib's server ALSO holds the registration until
            # pulled (no retract API), which is why the cap exists
            self._offers[uuid] = (now, data)
        try:
            server.await_pull(uuid, [data])
        except Exception:
            with self._lock:  # failed registration must not eat a slot
                self._offers.pop(uuid, None)
            raise
        return {
            "uuid": uuid,
            "address": self.address,
            "shape": list(data.shape),
            "dtype": str(data.dtype),
        }

    def offer(self, engine: JaxEngine, block_hashes: List[int]
              ) -> Optional[Dict[str, Any]]:
        """Gather the resident blocks ON DEVICE and offer them for one
        pull. Runs under ``run_exclusive``. Returns the rendezvous dict
        (wire-safe) or None when nothing is resident / the offer table is
        full (the decode side falls down the transport ladder)."""
        metas, data = _export_device(engine, block_hashes)
        if not metas:
            return None
        try:
            out = self.offer_array(data)
        except RuntimeError as e:
            import logging
            logging.getLogger(__name__).warning("direct offer refused: %s",
                                                e)
            return None
        out["blocks"] = [[h, local, parent] for h, local, parent in metas]
        return out

    def ack(self, uuid: int) -> None:
        """Drop a pulled offer's device array (and any expired ones)."""
        with self._lock:
            self._offers.pop(uuid, None)
            self._prune_offers_locked(time.time())

    def evict_expired_offers(self) -> int:
        """Drop every offer past ``OFFER_TTL_S`` (the decode side never
        pulled/acked — crashed or wedged); returns how many were
        reclaimed. The same pruning runs inline on offer/ack, so this is
        the explicit GC entry for sweeps and tests."""
        now = time.time()
        with self._lock:
            before = len(self._offers)
            self._prune_offers_locked(now)
            return before - len(self._offers)

    # -- destination (decode) side -----------------------------------------

    def pull(self, offer: Dict[str, Any]):
        """Pull an offered array device-to-device; returns the device
        array. Touches NO engine state — callers run it on any thread
        (with their own timeout) and commit via ``inject`` afterwards.
        A failed pull evicts the cached connection so a retry against a
        rebound peer reconnects."""
        import jax as _jax
        import jax.numpy as _jnp
        from jax.sharding import SingleDeviceSharding

        addr = offer["address"]
        server = self._ensure_server()
        with self._lock:
            conn = self._conns.get(addr)
        if conn is None:
            # connect OUTSIDE the lock: a black-holed peer must only
            # stall THIS pull thread, never an evict()/offer() waiting on
            # the lock from the event loop (the wedge the circuit breaker
            # exists to prevent). Two racing first pulls may both connect;
            # the loser's connection is dropped unreferenced — jaxlib's
            # TransferConnection exposes no close(), so GC is the only
            # teardown (same for MAX_CONNS/evict() removals).
            conn = server.connect(addr)
            with self._lock:
                if addr in self._conns:
                    conn = self._conns[addr]  # lost the race: reuse first
                else:
                    while len(self._conns) >= self.MAX_CONNS:
                        self._conns.pop(next(iter(self._conns)), None)
                    self._conns[addr] = conn
        spec = _jax.ShapeDtypeStruct(
            tuple(offer["shape"]), _jnp.dtype(offer["dtype"]),
            sharding=SingleDeviceSharding(_jax.devices()[0]))
        try:
            (data,) = conn.pull(offer["uuid"], [spec])
            _jax.block_until_ready(data)
        except Exception:
            self.evict(addr)
            raise
        return data

    def evict(self, address: str) -> None:
        """Drop a cached connection (failed/stalled peer — the next pull
        to the address reconnects)."""
        with self._lock:
            self._conns.pop(address, None)

    @staticmethod
    def inject(engine: JaxEngine, offer: Dict[str, Any], data) -> int:
        """Commit a pulled array's blocks into the cache. Runs under
        ``run_exclusive`` (the scatter reassigns ``engine.pages``)."""
        metas = [(b[0], b[1], b[2]) for b in offer["blocks"]]
        # trim gather padding before the scatter re-pads for its own ids
        return _inject_data(engine, metas, data[:, :len(metas)])

    def pull_and_inject(self, engine: JaxEngine,
                        offer: Dict[str, Any]) -> int:
        """Composite pull + inject (in-process/test convenience; the
        disagg handler runs the two phases separately so the network pull
        never blocks the engine's exclusive window)."""
        return self.inject(engine, offer, self.pull(offer))


def serve_kv_export_direct(engine: JaxEngine,
                           plane: DeviceTransferPlane):
    """RPC handler serving device-direct rendezvous offers: payload
    ``{"block_hashes": [...]}`` -> one offer dict (or an empty frame when
    nothing is resident); ``{"ack": uuid}`` releases a pulled offer's
    device array. Registered beside the frame/bulk exports."""

    async def handler(payload: Any, ctx):
        payload = payload or {}
        if payload.get("ack") is not None:
            plane.ack(int(payload["ack"]))
            yield {"acked": True}
            return
        hashes = list(payload.get("block_hashes", []))
        offer = await engine.run_exclusive(plane.offer, engine, hashes)
        yield offer if offer is not None else {}

    return handler


KV_EXPORT_DIRECT_ENDPOINT = "kv_export_direct"


__all__ = ["BlockPayload", "export_blocks", "inject_blocks",
           "export_frames", "inject_frame", "frame_arrays",
           "verify_frame", "FrameIntegrityError",
           "InjectPipeline", "inject_device_windowed", "pump_bulk_frames",
           "transfer_blocks_ici", "serve_kv_export",
           "serve_kv_export_bulk", "BLOCKS_PER_FRAME",
           "SCATTER_WINDOW_BLOCKS", "FRAME_WIRE_VERSION",
           "kv_transfer_defaults", "resolve_wire", "frame_crc_enabled",
           "cache_shard_layout", "kv_shard_payload",
           "ExportLeaseManager", "get_export_leases", "grant_export_lease",
           "release_export_lease", "stamp_export_lease",
           "stamp_frame_crcs", "export_ttl_s", "EXPORT_TTL_S",
           "DeviceTransferPlane", "serve_kv_export_direct",
           "KV_EXPORT_DIRECT_ENDPOINT"]
