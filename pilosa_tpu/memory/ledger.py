"""Device-memory budget ledger — one accountant for HBM bytes.

Clients (the tile-stack cache, the jit caches, the serving result
cache) register with a *reclaim callback* and account every resident
device allocation through :meth:`Ledger.reserve` / :meth:`release`.
The invariant the ledger maintains — and the concurrency tests pin —
is that the accounted total NEVER exceeds the budget: a reservation
that would cross it first drives reclaim across the OTHER clients
(coldest first, requester last), and is denied outright when not
enough cold bytes exist, in which case the caller serves its array
transiently without retaining it.

The budget resolves lazily on first pressure, in precedence order:
explicit ``configure(budget_bytes=...)`` > the
``PILOSA_TPU_MEMORY_BUDGET_BYTES`` env var > the real device memory
(``jax.local_devices()[0].memory_stats()``) minus a headroom fraction
> on a backend that reports no limit, an 8 GiB constant for the CPU
and an error for a TPU.  Lazy because eagerly touching
``jax.local_devices()`` at construction would initialize the backend
from every Executor ctor — including ones that never touch a device.

Clients are held by WEAK reference: a garbage-collected cache (tests
construct thousands of Executors) drops out of the accounting with its
arrays, so the ledger can never leak dead caches or their bytes.

Under the serving mesh (memory/placement.py) the one global pool
splits into PER-DEVICE budgets: the global budget divides evenly
across the mesh slots (``device_budget``), reservations carry the
owning slot (``reserve(..., device=slot)``) and are denied when THAT
device's labeled total would cross its share — a hot shard cannot
silently eat a remote chip's HBM.  Reclaim stays a global sweep
(clients shed coldest-first regardless of device; the per-device cap
re-checks after each round), device-less reservations (whole-stack
entries, jit executables, result payloads) stay bounded by the global
budget only, and ``device_bytes()`` feeds both the placer's balance
decision and the bench occupancy cells.
"""

from __future__ import annotations

import os
import threading
import weakref

from pilosa_tpu.obs import metrics

_FALLBACK_BUDGET = 8 << 30
_RECLAIM_ATTEMPTS = 3


class Client:
    """One registered device-byte owner.  ``reserve``/``release`` are
    the only mutators; ``bytes`` is the client's accounted total."""

    __slots__ = ("name", "_bytes", "_dev", "_reclaim_cb",
                 "_cold_ts_cb", "_ledger", "__weakref__")

    def __init__(self, name: str, ledger: "Ledger", reclaim_cb=None,
                 cold_ts_cb=None):
        self.name = name
        self._bytes = 0
        self._dev: dict[int, int] = {}   # mesh slot -> labeled bytes
        self._reclaim_cb = reclaim_cb
        self._cold_ts_cb = cold_ts_cb
        self._ledger = ledger

    @property
    def bytes(self) -> int:
        return self._bytes

    def reserve(self, nbytes: int, trigger: str = "reserve",
                device: int | None = None) -> bool:
        return self._ledger.reserve(self, nbytes, trigger=trigger,
                                    device=device)

    def release(self, nbytes: int, device: int | None = None):
        self._ledger.release(self, nbytes, device=device)

    def cold_ts(self) -> float:
        """Timestamp of this client's coldest resident entry (0 =
        unknown, treated as coldest) — the cross-client reclaim
        ordering hint."""
        if self._cold_ts_cb is None:
            return 0.0
        try:
            return float(self._cold_ts_cb())
        except Exception:
            return 0.0


class Ledger:
    def __init__(self, budget_bytes: int | None = None,
                 headroom_frac: float = 0.1):
        # explicit budget (configure/ctor); None = resolve lazily
        self._explicit = (int(budget_bytes)
                          if budget_bytes else None)
        self.headroom_frac = float(headroom_frac)
        self._budget: int | None = None
        self._clients: list[weakref.ref] = []
        self._lock = threading.Lock()
        # serving-mesh width (memory/placement.py keeps this current);
        # 1 = no per-device split, every device check degenerates to
        # the global one
        self._n_devices = 1

    # -- registration ---------------------------------------------------

    def register(self, name: str, reclaim=None, cold_ts=None) -> Client:
        """Register a client.  ``reclaim(nbytes) -> freed`` evicts the
        client's cold bytes under cross-client pressure (it must call
        ``client.release`` for what it drops and report the total);
        ``cold_ts() -> epoch seconds`` of its coldest entry orders the
        reclaim sweep.  The ledger keeps only a weak reference."""
        c = Client(name, self, reclaim_cb=reclaim, cold_ts_cb=cold_ts)
        with self._lock:
            self._clients.append(weakref.ref(c))
        return c

    def _live_locked(self) -> list[Client]:
        live, refs = [], []
        for r in self._clients:
            c = r()
            if c is not None:
                live.append(c)
                refs.append(r)
        self._clients = refs
        return live

    # -- budget ---------------------------------------------------------

    def set_budget(self, budget_bytes: int | None):
        """Explicit budget (None = auto-detect on next use).  Shrinking
        below the resident total reclaims down to the new bound."""
        with self._lock:
            self._explicit = (int(budget_bytes)
                              if budget_bytes else None)
            self._budget = self._explicit
            total = sum(c._bytes for c in self._live_locked())
            budget = self._budget
        if budget is not None:
            metrics.MEM_BUDGET.set(budget)
            if total > budget:
                self._reclaim(total - budget, requester=None,
                              trigger="shrink")

    def budget(self) -> int:
        b = self._budget
        if b is not None:
            return b
        # resolve OUTSIDE the lock: device init can be slow and must
        # not block concurrent release() calls
        b = self._detect()
        with self._lock:
            if self._budget is None:
                self._budget = b
            b = self._budget
        metrics.MEM_BUDGET.set(b)
        return b

    def _detect(self) -> int:
        if self._explicit:
            return self._explicit
        env = os.environ.get("PILOSA_TPU_MEMORY_BUDGET_BYTES")
        if env:
            try:
                n = int(env)
                if n > 0:
                    return n
            except ValueError:
                pass
        import jax
        dev = jax.local_devices()[0]
        stats = dev.memory_stats() or {}
        limit = (stats.get("bytes_limit")
                 or stats.get("bytes_reservable_limit"))
        if limit:
            return max(int(int(limit) * (1.0 - self.headroom_frac)),
                       1 << 20)
        if dev.platform == "tpu":
            # a chip whose size is unknown is not budgeted by guess
            raise RuntimeError(
                f"{dev} reports no memory limit (memory_stats() = "
                f"{stats!r}); set [memory] budget-bytes")
        return _FALLBACK_BUDGET  # CPU backends report no stats

    # -- devices --------------------------------------------------------

    def set_devices(self, n: int):
        """Serving-mesh width: the global budget splits evenly into
        per-device shares and device-labeled reservations are checked
        against their slot's share."""
        with self._lock:
            self._n_devices = max(int(n), 1)

    def device_budget(self) -> int:
        """One mesh slot's byte share of the global budget."""
        b = self.budget()
        with self._lock:
            return b // max(self._n_devices, 1)

    def device_bytes(self, n: int | None = None) -> list[int]:
        """Device-labeled resident bytes per mesh slot, summed across
        clients (the placer's balance signal + bench occupancy)."""
        with self._lock:
            nd = max(self._n_devices if n is None else int(n), 1)
            out = [0] * nd
            for c in self._live_locked():
                for slot, nb in c._dev.items():
                    if 0 <= slot < nd:
                        out[slot] += nb
            return out

    def _dev_total_locked(self, slot: int) -> int:
        return sum(c._dev.get(slot, 0)
                   for c in self._live_locked())

    # -- accounting -----------------------------------------------------

    @property
    def total_bytes(self) -> int:
        with self._lock:
            return sum(c._bytes for c in self._live_locked())

    def free_bytes(self) -> int:
        return max(self.budget() - self.total_bytes, 0)

    def reserve(self, client: Client, nbytes: int,
                trigger: str = "reserve",
                device: int | None = None) -> bool:
        """Account ``nbytes`` to ``client`` iff they fit the budget,
        reclaiming cold bytes across clients first.  False = denied —
        the caller must not retain the allocation.  ``device`` labels
        the bytes with their mesh slot and additionally enforces that
        slot's per-device share."""
        nbytes = int(nbytes)
        if nbytes <= 0:
            return True
        budget = self.budget()  # resolve before taking the lock
        with self._lock:
            nd = self._n_devices
        dev_budget = budget // nd if (device is not None
                                      and nd > 1) else None
        if nbytes > budget or (dev_budget is not None
                               and nbytes > dev_budget):
            metrics.MEM_DENIED.inc(client=client.name)
            return False
        for attempt in range(_RECLAIM_ATTEMPTS):
            with self._lock:
                total = sum(c._bytes for c in self._live_locked())
                need = max(total + nbytes - budget, 0)
                if need == 0 and dev_budget is not None:
                    dtot = self._dev_total_locked(device)
                    need = max(dtot + nbytes - dev_budget, 0)
                if need == 0:
                    client._bytes += nbytes
                    if device is not None:
                        client._dev[device] = (
                            client._dev.get(device, 0) + nbytes)
                    self._export_locked()
                    return True
            freed = self._reclaim(need, requester=client,
                                  trigger=trigger)
            if freed <= 0:
                break
        metrics.MEM_DENIED.inc(client=client.name)
        return False

    def release(self, client: Client, nbytes: int,
                device: int | None = None):
        nbytes = int(nbytes)
        if nbytes <= 0:
            return
        with self._lock:
            client._bytes = max(client._bytes - nbytes, 0)
            if device is not None:
                left = client._dev.get(device, 0) - nbytes
                if left > 0:
                    client._dev[device] = left
                else:
                    client._dev.pop(device, None)
            self._export_locked()

    # -- reclaim --------------------------------------------------------

    def _reclaim(self, need: int, requester: Client | None,
                 trigger: str) -> int:
        """Ask clients to shed ``need`` bytes: coldest clients first,
        the requester LAST — pressure in one cache evicts cold bytes
        in another before eating its own.  Callbacks run without the
        ledger lock (they call release() as they evict)."""
        metrics.MEM_RECLAIMS.inc(trigger=trigger)
        with self._lock:
            others = [c for c in self._live_locked()
                      if c is not requester and c._reclaim_cb is not None
                      and c._bytes > 0]
            me = (requester if requester is not None
                  and requester._reclaim_cb is not None else None)
        others.sort(key=lambda c: c.cold_ts())
        freed_total = 0
        for c in others + ([me] if me is not None else []):
            if freed_total >= need:
                break
            try:
                freed = int(c._reclaim_cb(need - freed_total) or 0)
            except Exception:
                freed = 0
            if freed > 0:
                freed_total += freed
                metrics.MEM_RECLAIMED.inc(freed, client=c.name)
        return freed_total

    def reclaim_frac(self, frac: float = 0.5,
                     trigger: str = "oom") -> int:
        """Shed a fraction of the resident total (the OOM backstop's
        pressure-relief sweep); returns bytes requested."""
        with self._lock:
            total = sum(c._bytes for c in self._live_locked())
        need = int(total * frac)
        if need > 0:
            self._reclaim(need, requester=None, trigger=trigger)
        return need

    def _export_locked(self):
        per: dict[str, int] = {}
        dev: dict[int, int] = {}
        for c in self._live_locked():
            per[c.name] = per.get(c.name, 0) + c._bytes
            for slot, nb in c._dev.items():
                dev[slot] = dev.get(slot, 0) + nb
        for name, nb in per.items():
            metrics.MEM_RESIDENT.set(nb, client=name)
        for slot, nb in dev.items():
            metrics.MEM_DEVICE_RESIDENT.set(nb, device=f"d{slot}")
