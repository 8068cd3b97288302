"""Device-side double-buffered input prefetch.

The host-side pipeline (``data/__init__.py``) overlaps batch ASSEMBLY with
training through its bounded-queue worker threads, but the trainer still
pays the device placement (``_prepare``: reshape + ``shard_batch``) on the
critical path of every step: pull batch, transfer, dispatch, in lockstep.
On a TPU that means the chip idles for the full host->device copy each
step. The standard pjit recipe (PAPERS: "Scalable Training of Language
Models using JAX pjit and TPUv4"; Mesh-TensorFlow's SPMD model assumes the
input feed never stalls the program) is to keep the device queue full:
while step N runs, batch N+1 is already ``device_put`` onto the mesh with
the exact sharding the compiled step expects — so the transfer is a true
overlap, not a layout-changing copy at dispatch time.

:func:`prefetch_to_device` wraps ANY host-batch iterator (composing with
``batch_iterator``'s host sharding, ``skip_batches`` resume fast-forward,
and thread prefetch — it only reorders WHEN transfers happen, never WHICH
indices are drawn, so exact-resume determinism is untouched) and yields
:class:`DeviceBatch` records the trainer dispatches directly.

``jax.device_put`` is asynchronous on accelerator backends: enqueueing
``depth`` transfers ahead costs host time only for the enqueue, and the
copies stream while the current step computes. On synchronous backends
(CPU tests) the wrapper degrades to a small lookahead buffer with
identical semantics. All placement is EXPLICIT ``device_put``
(``shard_batch``), so the wrapper composes with sanitizer mode's
``jax.transfer_guard("disallow")``.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import numpy as np

from ..obs import trace as trace_lib

__all__ = ["DeviceBatch", "prefetch_to_device"]


@dataclasses.dataclass(frozen=True)
class DeviceBatch:
    """One already-on-device batch plus the host-side facts the loop still
    needs after the numpy arrays are gone: the example count (the
    ``samples`` gauge reads it via ``get_batch_length`` BEFORE transfer,
    since the device tree may be reshaped to [n_micro, ...])."""

    arrays: Any          # pytree of jax.Array, placed with the step's sharding
    n_items: int         # examples in the originating host batch


def _default_length(batch: Dict[str, np.ndarray]) -> int:
    import jax

    return int(len(jax.tree_util.tree_leaves(batch)[0]))


def prefetch_to_device(
    iterator: Iterator[Dict[str, np.ndarray]],
    *,
    put: Callable[[Dict[str, np.ndarray]], Any],
    depth: int = 2,
    length_of: Optional[Callable[[Dict[str, np.ndarray]], int]] = None,
    stats: Optional[Any] = None,
    tracer: Any = trace_lib.NULL,
) -> Iterator[DeviceBatch]:
    """Yield :class:`DeviceBatch` with up to ``depth`` batches already
    placed on device ahead of the consumer.

    ``put`` maps a host batch to its device tree (the trainer passes its
    ``_prepare``: microbatch reshape + ``shard_batch`` with the data-axis
    sharding the AOT-compiled step was built for — placement at prefetch
    time is therefore the FINAL layout, no dispatch-time resharding).
    ``depth=2`` is classic double buffering: one batch consumed, one in
    flight. ``length_of`` extracts the example count from the host batch
    (the trainer's ``get_batch_length`` hook). ``stats`` (a
    ``perf.StallBreakdown``) receives ``data_wait_s`` (blocked on the
    host iterator) and ``h2d_wait_s`` (blocked in ``put``) attributions;
    ``tracer`` gets a ``data.host_wait`` and a ``data.h2d`` span between
    the same clock readings (the generator runs on its consumer's thread,
    so they nest under the span that pulled the batch).

    A finite upstream iterator drains cleanly: remaining buffered batches
    are yielded, then the wrapper stops. ``depth`` is validated eagerly
    (at the call, not at first iteration).
    """
    if depth < 1:
        raise ValueError(f"prefetch depth must be >= 1, got {depth}")
    length_of = length_of or _default_length

    def _gen() -> Iterator[DeviceBatch]:
        buf: "collections.deque[DeviceBatch]" = collections.deque()
        exhausted = False
        while True:
            # Refill BEFORE yielding: at hand-off time `depth` transfers
            # are enqueued, so the step the consumer is about to dispatch
            # overlaps with the copies already streaming.
            while not exhausted and len(buf) < depth:
                t0 = time.perf_counter()
                try:
                    with tracer.span("data.host_wait", "data"):
                        host = next(iterator)
                except StopIteration:
                    exhausted = True
                    break
                t1 = time.perf_counter()
                with tracer.span("data.h2d", "data"):
                    n = length_of(host)
                    arrays = put(host)
                t2 = time.perf_counter()
                if stats is not None:
                    stats.add("data_wait_s", t1 - t0)
                    stats.add("h2d_wait_s", t2 - t1)
                buf.append(DeviceBatch(arrays=arrays, n_items=n))
            if not buf:
                return
            yield buf.popleft()

    return _gen()
