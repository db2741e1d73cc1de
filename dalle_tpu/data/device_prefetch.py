"""Double-buffered device prefetch: overlap H2D transfer with the running step.

The host prefetchers in this package (``WebDataset.prefetch``, decode thread
pools) end at *host* numpy batches — every step still paid the
convert + ``jax.device_put`` on the device critical path, inside
``fit/batch_wait``+``fit/dispatch``. ``DevicePrefetcher`` keeps ``depth``
batches *already placed on the mesh* ahead of the consumer: while step N runs,
batches N+1..N+depth are converted and their transfers enqueued (``device_put``
is asynchronous on TPU — the copy engines overlap the running program), so a
steady-state pull returns an on-device batch in microseconds. See
docs/PERFORMANCE.md.

Semantics (tested in tests/test_overlap.py):
  * ordering — batches come out exactly in iterator order;
  * exhaustion — buffered batches drain before StopIteration;
  * errors — an exception from the source iterator or the put function is
    held until the already-buffered (good) batches are consumed, then raised.

Scope: this adapter is synchronous — it overlaps the *transfer* (device_put
enqueues immediately and the copy engines run under the step), not the
*source pull*. A slow host iterator still blocks ``__next__`` during the
refill; compose with a threaded host prefetcher (``WebDataset.prefetch``)
so the pull is a queue pop and the only remaining cost is the enqueue.

This module stays jax-free at import (the package rule for ``dalle_tpu.data``:
pure-numpy data workers must not drag jax in); ``prefetch_to_device``'s
default put imports lazily.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Iterable, Iterator, Optional

from ..obs.trace import span   # trace-only import: keeps this module jax-free


class DevicePrefetcher:
    """Iterator adapter holding ``depth`` put-applied items in flight.

    ``put`` maps one source item to its device-placed form (e.g. a trainer's
    ``_put_batch``). ``last_put_s`` is the host seconds the *consumed* item's
    put took — the ``t_h2d_s`` column of the step breakdown (the transfer
    itself overlaps earlier steps; this measures the host-side enqueue cost).
    """

    def __init__(self, it: Iterable, put: Callable, depth: int = 2):
        self._it = iter(it)
        self._put = put
        self.depth = max(int(depth), 1)
        self._buf: deque = deque()   # (put(item), put_seconds)
        self._err: Optional[Exception] = None
        self._done = False
        self.last_put_s = 0.0

    def _fill(self):
        while not self._done and self._err is None and len(self._buf) < self.depth:
            try:
                item = next(self._it)
            except StopIteration:
                self._done = True
                return
            except Exception as e:  # noqa: BLE001 - held, raised in order;
                # KeyboardInterrupt/SystemExit must NOT be parked (a held
                # interrupt would let training keep stepping — and maybe
                # checkpoint — for `depth` more iterations, or be dropped
                # entirely if the loop exits on its steps budget first)
                self._err = e
                return
            try:
                with span("data/h2d") as h2d:
                    placed = self._put(item)
                self._buf.append((placed, h2d.duration))
            except Exception as e:  # noqa: BLE001 - held, raised in order
                self._err = e
                return

    def __iter__(self) -> Iterator:
        return self

    def __next__(self):
        self._fill()
        if not self._buf:
            if self._err is not None:
                err, self._err = self._err, None
                self._done = True
                raise err
            raise StopIteration
        item, self.last_put_s = self._buf.popleft()
        return item


def prefetch_to_device(iterator: Iterable, mesh=None, depth: int = 2,
                       put: Optional[Callable] = None) -> DevicePrefetcher:
    """Wrap a host batch iterator so the next ``depth`` batches are already
    sharded onto ``mesh`` while the current one is consumed. With no ``put``,
    each item is pytree-``shard_batch``-ed onto the mesh (numpy leaves keep
    their dtypes); pass ``put`` for custom conversion/sharding — the trainers
    use their ``_put_batch`` so dtype coercion matches ``train_step``."""
    if put is None:
        if mesh is None:
            raise ValueError("prefetch_to_device needs a mesh or a put fn")
        from ..parallel import shard_batch   # lazy: keeps import jax-free

        def put(batch):
            return shard_batch(mesh, batch)

    return DevicePrefetcher(iterator, put, depth=depth)
