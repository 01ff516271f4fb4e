"""Double-buffered host->device staging for per-round federated arrays.

The experiment loop consumes the same logical inputs every round (xs, ys),
but at multi-host scale — or once per-round client sampling lands — each
round's arrays arrive from the host and the copy serializes with compute
unless it is dispatched while the PREVIOUS round still runs (ROADMAP
"Input-pipeline prefetch / double-buffering").

`RoundPrefetcher` is that overlap as a tiny ring:

  * `prefetch(*arrays)` starts the (asynchronous — `jax.device_put`
    dispatches and returns immediately) host->device copy of the NEXT
    round's arrays. Called right after the current round's compute is
    dispatched, the transfer rides out the round's wall-clock.
  * `get(*arrays)` returns device buffers for the CURRENT round: the
    prefetched ones when they match, else a blocking copy (first round /
    missed prefetch). Promoting the next buffer retires the previous
    round's: its device buffers are explicitly `delete()`d — the donation
    analog available from the host side (a host->device copy cannot
    alias into an existing device buffer through the public API), which
    bounds the ring to at most two resident copies instead of R.
  * Identity short-circuit: when the caller passes the SAME host arrays
    every round (the resident-dataset case every current config hits),
    the ring holds ONE device copy and both calls are O(1) no-ops — the
    historical `jnp.asarray(xs)`-once behavior, unchanged.

Matching is by host-array identity (`id`), not content: the prefetcher
exists to move bytes, not to dedupe equal values.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _key(arrays) -> tuple[int, ...]:
    return tuple(id(a) for a in arrays)


def _put(arrays, sharding=None) -> tuple:
    # device_put is async: it enqueues the transfer and returns
    # immediately; consumers block only when they actually need the bytes.
    # Each entry is (buffer, owned): `owned` is False when the "copy" was
    # an identity (the caller's array was already device-resident), in
    # which case retirement must NOT delete it — it is the caller's.
    out = []
    for a in arrays:
        buf = jax.device_put(
            a if sharding is not None else jnp.asarray(a), sharding
        )
        out.append((buf, buf is not a))
    return tuple(out)


def _bufs(entries) -> tuple:
    return tuple(b for b, _ in entries)


def _delete(entries) -> None:
    for b, owned in entries:
        if not owned:
            continue
        try:
            b.delete()
        except Exception:  # already donated/deleted — nothing to free
            pass


class RoundPrefetcher:
    def __init__(self, sharding=None):
        """`sharding` (e.g. `parallel.client_sharding(mesh)`) places every
        staged array with it — each device receives only its own client
        block. None keeps the default single-device placement."""
        self._sharding = sharding
        self._cur = self._next = None
        self._cur_key = self._next_key = None

    def prefetch(self, *arrays) -> None:
        """Begin the async copy of the next round's arrays (no-op when
        they are already resident as the current or staged buffers)."""
        key = _key(arrays)
        if key in (self._cur_key, self._next_key):
            return
        if self._next is not None:
            _delete(self._next)  # superseded before use
        self._next, self._next_key = _put(arrays, self._sharding), key

    def get(self, *arrays) -> tuple:
        """Device buffers for this round's arrays (prefetched if staged,
        else copied now). Retires — deletes — the previous round's
        buffers when a staged buffer is promoted (only buffers this ring
        copied itself; a caller-owned device array passed straight
        through is never deleted)."""
        key = _key(arrays)
        if key == self._cur_key:
            return _bufs(self._cur)
        stale = self._cur
        if key == self._next_key:
            self._cur, self._cur_key = self._next, self._next_key
            self._next = self._next_key = None
        else:
            self._cur, self._cur_key = _put(arrays, self._sharding), key
        if stale is not None:
            _delete(stale)
        return _bufs(self._cur)
