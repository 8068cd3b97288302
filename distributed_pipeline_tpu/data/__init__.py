"""Host-sharded infinite data pipeline.

API parity with the reference loader (``/root/reference/data/__init__.py:1-38``):
``load_data_from_args(split, data_dir, batch_size, deterministic, loop,
num_loader_proc)`` returning an infinite iterator of batches, plus the
``infinite_loader_from_iterable`` / ``infinite_loader_from_object`` helpers.

TPU-native redesign instead of torch ``DataLoader``:

* **Host sharding** — each JAX process draws a disjoint stride of the global
  index stream (``process_index :: process_count``), matching the reference's
  per-rank-loads-its-own-batch semantics (global batch = batch_size x hosts,
  reference trainer.py:89) without any sampler object.
* **Static shapes** — every batch is exactly ``[batch_size, seq_len]``; the
  tail of an epoch wraps around rather than emitting a ragged batch, so the
  jitted train step never recompiles.
* **Background prefetch** — a bounded queue fed by worker threads overlaps
  host-side batch assembly with device compute (the role of torch's
  ``num_workers``/``persistent_workers``, reference data/__init__.py:17-23).
  Threads, not processes: item synthesis is numpy-bound and the arrays go
  straight to ``jax.device_put`` without pickling.
"""

from __future__ import annotations

import copy
import queue
import threading
from typing import Any, Dict, Iterable, Iterator, Optional

import numpy as np

from ..obs import trace as trace_lib
from .dataset import (
    BOS_ID,
    EOS_ID,
    PAD_ID,
    SEP_ID,
    CustomDataset,
    JsonlSeq2SeqDataset,
    SyntheticLMDataset,
    SyntheticSeq2SeqDataset,
)
from .device_prefetch import DeviceBatch, prefetch_to_device

__all__ = [
    "load_data_from_args",
    "infinite_loader_from_iterable",
    "infinite_loader_from_object",
    "batch_iterator",
    "skip_batches_for_samples",
    "prefetch_to_device",
    "DeviceBatch",
    "CustomDataset",
    "JsonlSeq2SeqDataset",
    "SyntheticLMDataset",
    "SyntheticSeq2SeqDataset",
]


def skip_batches_for_samples(consumed_samples: int, batch_size: int,
                             process_count: int = 1) -> int:
    """Elastic-resume fast-forward: ``skip_batches`` for a stream that must
    land AFTER ``consumed_samples`` globally-consumed examples.

    Across a topology change the unit "steps" stops meaning anything —
    a checkpoint written at global batch 2B and resumed at global batch B
    must skip TWICE the saved step count of the new stream's batches to
    keep the sample sequence aligned. Global samples consumed
    (``step * global_batch`` at save time, recorded in the checkpoint's
    meta sidecar) is the topology-invariant position. Same topology
    degenerates to ``skip == resume_step`` exactly, preserving the
    bit-identical same-shape resume; when the new global batch does not
    divide the consumed count the position rounds DOWN (a partial
    batch's samples are re-consumed — the loss-continuity, not
    bit-identity, contract of a shrink/grow resume)."""
    gb = batch_size * max(process_count, 1)
    if gb <= 0:
        raise ValueError(f"global batch must be positive, got {gb}")
    return max(0, int(consumed_samples)) // gb


def infinite_loader_from_object(obj: Iterable) -> Iterator:
    """Replay an exhaustible iterable forever by deep-copying it each epoch
    and yielding its items (role of reference data/__init__.py:30-33)."""
    while True:
        yield from copy.deepcopy(obj)


def infinite_loader_from_iterable(it: Iterable) -> Iterator:
    """``while True: yield from`` for restartable iterables (reference
    data/__init__.py:36-38)."""
    while True:
        yield from it


def _host_index_stream(n_items: int, *, shuffle: bool, seed: int,
                       process_index: int, process_count: int,
                       loop: bool, skip_items: int = 0) -> Iterator[int]:
    """Yield this host's slice of the (optionally shuffled) global index
    sequence; epochs reshuffle with a different fold of the seed.

    ``skip_items`` fast-forwards the stream by that many items in O(1):
    the order is a pure function of (seed, epoch), so whole epochs are
    jumped arithmetically and only the first yielded epoch is sliced —
    this is what makes checkpoint resume replay the EXACT data order an
    uninterrupted run would have seen (the reference restarts its
    DataLoader from scratch on resume, silently repeating early batches).
    """
    # Every host must yield the SAME number of items per epoch, or multi-host
    # collectives desync (host 0's stride can be 1 longer): trim to the floor.
    per_host = n_items // process_count
    if per_host == 0:
        raise ValueError(
            f"dataset of {n_items} items cannot feed {process_count} hosts "
            f"(at least one item per host per epoch required)")
    epoch = skip_items // per_host
    offset = skip_items % per_host
    if not loop and epoch > 0:
        return  # skipped past the single epoch
    while True:
        if shuffle:
            order = np.random.default_rng(
                (seed * 0x51ED2701 + epoch) & 0xFFFFFFFFFFFFFFFF
            ).permutation(n_items)
        else:
            order = np.arange(n_items)
        sl = order[process_index::process_count][:per_host]
        yield from sl[offset:].tolist()
        offset = 0
        if not loop:
            return
        epoch += 1


def batch_iterator(dataset: Any, batch_size: int, *, shuffle: bool = True,
                   seed: int = 0, loop: bool = True,
                   process_index: int = 0, process_count: int = 1,
                   num_workers: int = 0, prefetch: int = 4,
                   skip_batches: int = 0) -> Iterator[Dict[str, np.ndarray]]:
    """Assemble fixed-shape batches from any ``__len__``/``__getitem__``
    dataset, host-sharded and optionally thread-prefetched. ``skip_batches``
    fast-forwards past that many already-consumed batches in O(1) (exact
    data-order resume; see ``_host_index_stream``)."""
    n = len(dataset)
    if n < batch_size * process_count and not loop:
        raise ValueError(
            f"dataset of {n} items cannot fill one global batch of "
            f"{batch_size}x{process_count} without looping")

    def gen(worker_id: int = 0, stride: int = 1) -> Iterator[Dict[str, np.ndarray]]:
        """Yield every ``stride``-th batch starting at ``worker_id``. Skipped
        batches only consume (cheap) indices, never materialize items — this
        is what lets N producer threads split the item-synthesis work while
        the interleaved stream stays identical to the single-producer order.
        """
        idx_stream = _host_index_stream(
            n, shuffle=shuffle, seed=seed, process_index=process_index,
            process_count=process_count, loop=loop,
            skip_items=skip_batches * batch_size)
        # b continues from the global batch counter so the worker-stride
        # assignment (b % stride) stays identical to an unskipped stream.
        b = skip_batches
        while True:
            mine = b % stride == worker_id
            taken = 0
            items = []
            for idx in idx_stream:
                taken += 1
                if mine:
                    items.append(dataset[idx])
                if taken == batch_size:
                    break
            if taken < batch_size:
                return  # non-loop tail: drop ragged batch (static shapes)
            if mine:
                yield {k: np.stack([it[k] for it in items])
                       for k in items[0]}
            b += 1

    if num_workers <= 0:
        return gen()
    return _prefetched(gen, num_workers=num_workers, depth=prefetch,
                       start_batch=skip_batches)


def _prefetched(gen_factory, *, num_workers: int, depth: int,
                start_batch: int = 0) -> Iterator:
    """Run ``num_workers`` producer threads, each materializing its
    ``worker_id :: num_workers`` stripe of the batch sequence (the role of
    torch's ``num_workers`` processes — threads suffice here because item
    synthesis is released-GIL numpy). The consumer round-robins the
    per-worker queues, so the delivered order is identical to the
    single-producer stream regardless of thread scheduling. ``start_batch``
    is the global index of the first batch the producers will emit (a
    resumed stream): the round-robin must start at that worker's queue or
    every delivery is rotated by ``start_batch % num_workers``.
    """
    _END = object()
    stop = threading.Event()
    queues = [queue.Queue(maxsize=max(1, depth)) for _ in range(num_workers)]

    def _put(q: "queue.Queue", item) -> bool:
        # Bounded put that notices consumer shutdown, so an abandoned
        # loop=True iterator doesn't leave a thread blocked forever
        # holding a queue full of batches.
        while not stop.is_set():
            try:
                q.put(item, timeout=0.5)
                return True
            except queue.Full:
                continue
        return False

    def worker(wid: int) -> None:
        q = queues[wid]
        tr = trace_lib.FOLLOW   # one is_enabled() a batch outside a session
        try:
            batches = gen_factory(worker_id=wid, stride=num_workers)
            while True:
                # the making of one batch on this thread (which of it holds
                # the GIL against the step loop); the wait in _put is not in
                with tr.span("data.assemble", "data",
                             args={"worker": wid} if tr.enabled else None):
                    batch = next(batches, _END)
                if batch is _END or not _put(q, batch):
                    break
            _put(q, _END)
        except BaseException as e:  # propagate to the consumer, don't die silent
            _put(q, e)

    for wid in range(num_workers):
        threading.Thread(target=worker, args=(wid,), daemon=True).start()
    try:
        b = start_batch
        while True:
            item = queues[b % num_workers].get()
            if item is _END:
                # Batch b doesn't exist -> no later batch does either (the
                # stream is exhausted in order); drain nothing, just stop.
                return
            if isinstance(item, BaseException):
                raise item
            yield item
            b += 1
    finally:
        stop.set()  # reached on GeneratorExit/close as well as normal end


def _build_dataset(dataset: str, data_dir: str, split: str, *, seq_len: int,
                   vocab_size: int, seed: int) -> Any:
    """Dataset registry: jsonl corpora when ``data_dir`` is given, synthetic
    streams otherwise (the reference's TODO hook, data/__init__.py:13-14)."""
    if data_dir:
        return JsonlSeq2SeqDataset(data_dir, split, seq_len=seq_len,
                                   vocab_size=vocab_size)
    # Validation streams draw from a disjoint seed fold so eval is held out.
    fold = seed if split == "train" else seed + 7919
    if dataset in ("synthetic-lm", "lm", "gpt2"):
        return SyntheticLMDataset(seq_len=seq_len, vocab_size=vocab_size,
                                  seed=fold)
    return SyntheticSeq2SeqDataset(seq_len=seq_len, vocab_size=vocab_size,
                                   seed=fold)


def load_data_from_args(split: str = "train", data_dir: str = "",
                        batch_size: int = 1, deterministic: bool = False,
                        loop: bool = True, num_loader_proc: int = 0,
                        *, dataset: str = "synthetic-seq2seq",
                        seq_len: int = 128, vocab_size: int = 8192,
                        seed: int = 0, data_loader_workers: int = 0,
                        host_sharded: bool = True, skip_batches: int = 0,
                        **_unused: Any) -> Iterator[Dict[str, np.ndarray]]:
    """The reference's loader entry point (``data/__init__.py:1-27``), with
    identical call semantics: ``deterministic`` disables shuffling (used for
    the valid split, reference run/train.py:63), ``loop`` wraps the epoch
    infinitely, ``num_loader_proc`` enables background prefetch
    (``data_loader_workers``, the ``DataSettings`` field name, is an accepted
    alias so ``load_data_from_args(**settings.dict())`` wires prefetch).
    ``batch_size`` is per host; global batch = ``batch_size * process_count``.
    ``host_sharded=False`` gives every host the SAME stream (required when a
    batch feeds a collective computation as a replicated array — e.g. the
    eval-decode callback — where per-host divergence would be silent UB).
    ``skip_batches`` fast-forwards the stream in O(1) so a resumed run sees
    the exact batches an uninterrupted one would have (run/train.py passes
    the resume step; one train step consumes one batch)."""
    import jax

    ds = _build_dataset(dataset, data_dir, split, seq_len=seq_len,
                        vocab_size=vocab_size, seed=seed)
    return batch_iterator(
        ds, batch_size,
        shuffle=not deterministic,
        seed=seed,
        loop=loop,
        process_index=jax.process_index() if host_sharded else 0,
        process_count=jax.process_count() if host_sharded else 1,
        num_workers=max(num_loader_proc, data_loader_workers),
        skip_batches=skip_batches,
    )
