"""Device-resident flat gradient pipeline: flatten, quantize and stream the
mean-grad tree OFF the accelerator without blocking the dispatch stream.

The legacy boundary seam (``collaborative/optimizer.py``) crossed the
jit<->host boundary one LEAF at a time: ``jax.device_get`` per gradient
tensor (O(leaves) transfers at full fp32 width), then a host-side
``TreeLayout.flatten_into`` pass, then — under a lossy wire format — a host
encode (fp32 -> fp16/uint8) of bytes that had just crossed PCIe at 4 bytes
per element. This module moves all of that onto the device:

- **flatten**: one jitted program concatenates the tree into ONE flat fp32
  buffer in the same sorted-name ``TreeLayout`` order as the host flatten —
  bit-identical by construction (same per-element ``x / n`` mean and
  ``x * scale`` clip, same ordering; locked by the parity suite in
  ``tests/test_device_flat.py``);
- **mean + contribution clip**: the ``grad_acc / n`` division and the
  contrib-clip global-norm reduce ride the same fused program — ONE
  ``vdot`` over the flat buffer instead of a Python-level sum of per-leaf
  reductions;
- **error feedback**: the quantization residual (DGC-style, see
  ``collaborative/error_feedback.py`` for the lineage and the commit
  discipline this class mirrors) lives on device and is folded into the
  contribution inside the same program;
- **quantize**: under ``float16``/``uint8`` wire formats the compressed
  representation is produced ON DEVICE, so the PCIe transfer carries 2 or
  16 bits per element instead of 32 — the host codec becomes the
  decode-only leg (fp16 widens during one ``np.copyto``; uint8 dequantizes
  per block with its own affine grid, matching ``native.quantize_uint8``
  semantics per block);
- **streaming**: the program returns the buffer pre-split into fixed-size
  chunks; ``copy_to_host_async`` is issued on every chunk at launch, so the
  transfer overlaps whatever the caller does next (the next micro-batches'
  accumulation under overlap averaging, matchmaking otherwise) and
  ``FlatFetch.result()`` only ever pays the NOT-yet-arrived remainder —
  the ``d2h_stream`` step phase / ``opt.d2h_stream`` telemetry event
  record how much of the transfer was actually exposed.

Dtype contract: only floating-point leaves are accepted (fp32/bf16/fp16 —
everything the fp32 flat layout represents exactly). Integer or boolean
leaves are REFUSED at build time with ``ValueError`` — averaging them is
meaningless and the host path would have silently cast; same stance as the
checkpoint manifest's fp32-roundtrip refusal.
"""
from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from dedloc_tpu.averaging.partition import FlatTree, TreeLayout
from dedloc_tpu.telemetry import registry as telemetry
from dedloc_tpu.telemetry.registry import monotonic_clock
from dedloc_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# fp32 elements per D2H chunk (4 MB): big enough that per-chunk dispatch
# overhead vanishes, small enough that the first chunks land while the rest
# are still in flight. Also the uint8 quantization BLOCK: each chunk gets
# its own affine (lo, scale) grid, so a cold embedding row cannot flatten
# the grid of the whole vector.
DEFAULT_D2H_CHUNK = 1 << 20


def named_device_leaves(tree) -> List[Tuple[str, Any]]:
    """(name, leaf) pairs with the SAME deterministic naming as the
    optimizer's host-side ``tree_to_named`` (jax keystr paths), so the
    device pipeline's sorted spec matches the host TreeLayout exactly."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path) or f"leaf{i}"
        out.append((name, leaf))
    return out


def _chunk_bounds(total: int, chunk: int) -> List[Tuple[int, int]]:
    bounds = []
    offset = 0
    while offset < total:
        bounds.append((offset, min(offset + chunk, total)))
        offset = bounds[-1][1]
    return bounds


# module-level program cache: jitted prepare fns keyed by their static
# signature, so pipeline instances over identical schemas (tests build many
# optimizers over the same tiny trees) share one compiled program
_PREPARE_CACHE: Dict[Tuple, Callable] = {}


def _build_prepare(order, total, chunk, compression, use_ef, use_clip):
    """Compile (with caching) the fused flatten(+mean+clip+EF+quantize+
    split) program for one (spec, options) signature."""
    key = (tuple(order), total, chunk, compression, use_ef, use_clip)
    cached = _PREPARE_CACHE.get(key)
    if cached is not None:
        return cached
    import jax
    import jax.numpy as jnp

    bounds = _chunk_bounds(total, chunk)

    def grad_flat_prepare(leaves, n, cap, residual):
        by_spec = [None] * len(leaves)
        for leaf, pos in zip(leaves, order):
            by_spec[pos] = leaf.astype(jnp.float32).reshape(-1)
        flat = (
            jnp.concatenate(by_spec) if by_spec
            else jnp.zeros((0,), jnp.float32)
        )
        # the grad_acc / n mean, fused — a DIVISION, not a reciprocal
        # multiply, so the result is bit-identical to the host path's
        # per-leaf ``g / n`` (x/3 != x*(1/3) in fp32)
        flat = flat / n
        if use_clip:
            # contrib clip: ONE global-norm reduce on the flat buffer
            # (legacy: a Python-level sum of per-leaf vdots)
            gnorm = jnp.sqrt(jnp.vdot(flat, flat).real)
            flat = flat * jnp.minimum(1.0, cap / (gnorm + 1e-12))
        contrib = flat + residual if use_ef else flat

        if compression == "none":
            wire = tuple(contrib[lo:hi] for lo, hi in bounds)
            return wire, (), contrib if use_ef else None
        if compression == "float16":
            q = contrib.astype(jnp.float16)
            wire = tuple(q[lo:hi] for lo, hi in bounds)
            if not use_ef:
                return wire, (), None
            return wire, (), contrib - q.astype(jnp.float32)
        if compression == "uint8":
            n_blocks = len(bounds)
            pad = n_blocks * chunk - total
            grid = jnp.pad(contrib, (0, pad)).reshape(n_blocks, chunk)
            valid = (
                jnp.arange(n_blocks * chunk).reshape(n_blocks, chunk) < total
            )
            lo = jnp.min(jnp.where(valid, grid, jnp.inf), axis=1)
            hi = jnp.max(jnp.where(valid, grid, -jnp.inf), axis=1)
            # native.quantize_uint8 per block: scale (hi-lo)/255, 0 -> 1.0
            scale = (hi - lo) / 255.0
            scale = jnp.where(scale == 0.0, 1.0, scale)
            q = jnp.clip(
                jnp.rint((grid - lo[:, None]) / scale[:, None]), 0, 255
            ).astype(jnp.uint8)
            wire = tuple(
                q[i, : b_hi - b_lo] for i, (b_lo, b_hi) in enumerate(bounds)
            )
            if not use_ef:
                return wire, (lo, scale), None
            dq = q.astype(jnp.float32) * scale[:, None] + lo[:, None]
            new_residual = contrib - dq.reshape(-1)[:total]
            return wire, (lo, scale), new_residual
        raise ValueError(f"unknown compression {compression!r}")

    fn = jax.jit(grad_flat_prepare)
    _PREPARE_CACHE[key] = fn
    return fn


class FlatFetch:
    """One in-flight device->host transfer of a flat contribution.

    ``result()`` blocks until every chunk has landed, decodes into the
    pipeline's host buffer and returns a ``FlatTree`` over it; it is
    idempotent and thread-safe (the averager resolves it on an executor
    thread, overlapped with matchmaking). ``exposed_wait_s`` is how long
    the FIRST ``result()`` call actually blocked — the portion of the
    transfer nothing else hid.
    """

    def __init__(
        self,
        pipeline: "DeviceFlatPipeline",
        wire_chunks,
        quant_meta,
        new_residual,
        host_buffer: np.ndarray,
    ) -> None:
        self.pipeline = pipeline
        self.spec = pipeline.spec
        self._wire = wire_chunks
        self._meta = quant_meta
        self._new_residual = new_residual
        self._buffer = host_buffer
        self._lock = threading.Lock()
        self._result: Optional[FlatTree] = None
        self.launched_at = monotonic_clock()
        self.exposed_wait_s = 0.0
        self.wire_bytes = sum(int(c.nbytes) for c in wire_chunks) + sum(
            int(m.nbytes) for m in quant_meta
        )

    def result(self) -> FlatTree:
        with self._lock:
            if self._result is not None:
                return self._result
            t0 = monotonic_clock()
            buf = self._buffer
            pipeline = self.pipeline
            if pipeline.compression == "uint8":
                _lo, scale = (np.asarray(m) for m in self._meta)
                for i, (lo_i, hi_i) in enumerate(pipeline.bounds):
                    out = buf[lo_i:hi_i]
                    np.copyto(out, np.asarray(self._wire[i]),
                              casting="unsafe")
                    out *= np.float32(scale[i])
                    out += np.float32(_lo[i])
            else:
                # fp32 passthrough, or the fp16 decode-only leg: the widen
                # happens inside one strided copy into the host buffer
                for (lo_i, hi_i), chunk in zip(pipeline.bounds, self._wire):
                    np.copyto(buf[lo_i:hi_i], np.asarray(chunk),
                              casting="unsafe")
            self.exposed_wait_s = max(0.0, monotonic_clock() - t0)
            self._wire = ()  # release device references
            self._meta = ()
            self._result = pipeline.layout.tree_view(buf)
            pipeline._record_fetch(self)
            return self._result


class DeviceFlatPipeline:
    """Jitted companion to ``TreeLayout`` for one stable gradient schema.

    Built lazily from the first boundary's mean-grad tree; ``fetch()``
    launches the fused device program plus async host copies and returns a
    ``FlatFetch``. Host buffers are DOUBLE-buffered: at most two fetches
    may be outstanding (the overlap path holds one across boundaries while
    the sync fallback starts another) — the returned ``FlatTree`` is valid
    until the next-but-one ``fetch``.

    Error feedback mirrors ``collaborative/error_feedback.py`` exactly:
    ``fetch(use_ef=True)`` folds the committed residual into the
    contribution and computes this round's candidate residual on device;
    ``commit(fetch)`` adopts it ONLY when the round landed, ``reset()``
    drops it after a resync. Unlike the host class, a committed residual
    here also covers the D2H quantization leg — the device-quantized
    representation IS what the host (and therefore the wire) sees, so even
    a singleton round that never touched the network has crossed the lossy
    leg and must commit, not reset (the optimizer handles that switch).
    """

    def __init__(
        self,
        spec: Sequence[Tuple[str, Tuple[int, ...], np.dtype]],
        order: Sequence[int],
        compression: str = "none",
        chunk_elems: int = DEFAULT_D2H_CHUNK,
        telemetry_registry=None,
    ) -> None:
        self.spec = list(spec)
        self.order = tuple(order)
        self.layout = TreeLayout(self.spec)
        self.total = self.layout.total_size
        self.compression = compression
        self.chunk_elems = max(1, int(chunk_elems))
        self.bounds = _chunk_bounds(self.total, self.chunk_elems)
        self.telemetry = telemetry_registry
        self._prepare_cache: Dict[Tuple[bool, bool], Callable] = {}
        self._residual = None  # device flat [total], lazily zeros
        self._buffers = [
            np.empty((self.total,), np.float32) for _ in range(2)
        ]
        self._next_buffer = 0
        self.fetches = 0
        self.wire_bytes_total = 0

    # ------------------------------------------------------------- factory

    @classmethod
    def for_tree(
        cls,
        tree,
        compression: str = "none",
        chunk_elems: int = DEFAULT_D2H_CHUNK,
        telemetry_registry=None,
    ) -> "DeviceFlatPipeline":
        """Build from a gradient pytree (device or host leaves). Raises
        ``ValueError`` on non-floating leaves — the refusal contract."""
        named = named_device_leaves(tree)
        for name, leaf in named:
            dtype = np.dtype(getattr(leaf, "dtype", np.asarray(leaf).dtype))
            # kind 'f' covers the IEEE floats; bfloat16 registers as a
            # void-kind extension dtype but widens exactly to fp32
            if dtype.kind != "f" and dtype.name != "bfloat16":
                raise ValueError(
                    f"device flat pipeline refuses non-float leaf "
                    f"{name!r} ({dtype}): the fp32 flat layout cannot "
                    "represent it (checkpoint-path refusal semantics)"
                )
        names = sorted(name for name, _leaf in named)
        index = {n: i for i, n in enumerate(names)}
        spec = [None] * len(named)
        order = []
        for name, leaf in named:
            shape = tuple(getattr(leaf, "shape", np.asarray(leaf).shape))
            spec[index[name]] = (name, shape, np.dtype(np.float32))
            order.append(index[name])
        return cls(
            spec, order, compression=compression, chunk_elems=chunk_elems,
            telemetry_registry=telemetry_registry,
        )

    def matches_tree(self, tree) -> bool:
        named = named_device_leaves(tree)
        if len(named) != len(self.spec):
            return False
        by_name = {
            name: tuple(getattr(leaf, "shape", np.asarray(leaf).shape))
            for name, leaf in named
        }
        return all(
            by_name.get(name) == tuple(shape)
            for name, shape, _dtype in self.spec
        )

    # ------------------------------------------------------------ EF state

    @property
    def ef_enabled(self) -> bool:
        return self.compression != "none"

    def _residual_dev(self):
        import jax.numpy as jnp

        if self._residual is None:
            self._residual = jnp.zeros((self.total,), jnp.float32)
        return self._residual

    def commit(self, fetch: FlatFetch) -> None:
        """Adopt the round's residual — call only when the round landed."""
        if fetch._new_residual is not None:
            self._residual = fetch._new_residual

    def reset_residual(self) -> None:
        """Drop the carried residual (post-resync: it belongs to gradients
        computed on params this peer no longer holds)."""
        self._residual = None

    def residual_norm_launch(self):
        """The carried residual's L2 norm as a device scalar whose transfer
        to the host has STARTED (``float()`` of it later costs no wait once
        the device got there), or 0.0 with no residual: a reader on the
        round's path launches it and reads it at its next boundary."""
        if self._residual is None:
            return 0.0
        import jax.numpy as jnp

        norm = jnp.sqrt(jnp.vdot(self._residual, self._residual).real)
        norm.copy_to_host_async()
        return norm

    def residual_norm(self) -> float:
        return float(self.residual_norm_launch())

    # --------------------------------------------------------------- fetch

    def _prepare_fn(self, use_ef: bool, use_clip: bool) -> Callable:
        key = (use_ef, use_clip)
        fn = self._prepare_cache.get(key)
        if fn is None:
            fn = _build_prepare(
                self.order, self.total, self.chunk_elems, self.compression,
                use_ef, use_clip,
            )
            self._prepare_cache[key] = fn
        return fn

    def fetch(
        self,
        tree,
        n: float = 1.0,
        clip_cap: Optional[float] = None,
        use_ef: bool = True,
    ) -> FlatFetch:
        """Launch the fused prepare program + async D2H for ``tree``.

        ``n`` folds the accumulator mean (the micro-batch count);
        ``clip_cap`` enables the contrib clip at that cap; ``use_ef``
        gates the residual fold (the optimizer passes False for
        zero-weight/gated rounds, matching the host path).
        """
        import jax
        import jax.numpy as jnp

        use_ef = bool(use_ef and self.ef_enabled)
        use_clip = clip_cap is not None
        leaves = [leaf for _name, leaf in named_device_leaves(tree)]
        residual = (
            self._residual_dev() if use_ef
            else jnp.zeros((0,), jnp.float32)
        )
        wire, meta, new_residual = self._prepare_fn(use_ef, use_clip)(
            leaves,
            jnp.float32(n),
            jnp.float32(clip_cap if use_clip else 0.0),
            residual,
        )
        for chunk in wire:
            chunk.copy_to_host_async()
        for m in meta:
            m.copy_to_host_async()
        buf = self._buffers[self._next_buffer]
        self._next_buffer = (self._next_buffer + 1) % len(self._buffers)
        return FlatFetch(self, wire, meta, new_residual, buf)

    def _record_fetch(self, fetch: FlatFetch) -> None:
        self.fetches += 1
        self.wire_bytes_total += fetch.wire_bytes
        tele = telemetry.resolve(self.telemetry)
        if tele is not None:
            tele.counter("opt.d2h_bytes").inc(fetch.wire_bytes)
            tele.counter("opt.d2h_exposed_s").inc(fetch.exposed_wait_s)
            tele.histogram("opt.d2h_wait_s").observe(fetch.exposed_wait_s)
            tele.event(
                "opt.d2h_stream",
                bytes=fetch.wire_bytes,
                exposed_s=fetch.exposed_wait_s,
                chunks=len(self.bounds),
                compression=self.compression,
            )
