"""OOM backstop — absorb RESOURCE_EXHAUSTED instead of failing queries.

XLA surfaces HBM exhaustion as an ``XlaRuntimeError`` whose message
leads with ``RESOURCE_EXHAUSTED`` (or ``Out of memory`` on some
backends).  Before this module that exception rode straight up to the
client as a failed query.  :func:`guarded` wraps every device dispatch
on the stacked/serving paths with the recovery ladder:

1. catch an OOM, run a ledger-driven pressure-relief sweep (shed half
   the accounted resident bytes across ALL clients + a gc pass so the
   dropped device buffers actually return to the allocator);
2. ONE bounded retry of the same dispatch;
3. still failing: degraded mode — re-execute the SAME plan on the host
   CPU backend (bit-exact by construction: identical program, the
   leaves fetched to host numpy), so the query answers slowly instead
   of erroring.

``inject_oom(n)`` is the test seam: the next ``n`` guarded dispatches
raise a synthetic RESOURCE_EXHAUSTED before running, which is how
tests/test_memory.py proves absorption without a real 16 GiB working
set.  Since ISSUE 6 the seam is a registered
fault point (``device-oom`` in obs/faults.py) — this function is the
backward-compatible wrapper, and the fault can equally be armed via
the registry's config/env spec alongside the rpc/node faults."""

from __future__ import annotations

import gc
import os

from pilosa_tpu.obs import faults, metrics

# config [memory] / PILOSA_TPU_MEMORY_OOM_RETRY / _HOST_FALLBACK
OOM_RETRY = os.environ.get("PILOSA_TPU_MEMORY_OOM_RETRY", "1") != "0"
HOST_FALLBACK = os.environ.get(
    "PILOSA_TPU_MEMORY_HOST_FALLBACK", "1") != "0"

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "Ran out of memory")

_warned_degraded = False


class InjectedOOM(RuntimeError):
    """Synthetic RESOURCE_EXHAUSTED raised by the device-oom fault."""


def inject_oom(n: int = 1):
    """Make the next ``n`` guarded dispatches fail with a synthetic
    RESOURCE_EXHAUSTED (test hook).  Registry-backed: arms the
    ``device-oom`` fault point, replacing any prior arming (the
    original seam's set-not-add semantics, which the tests rely on)."""
    faults.clear("device-oom")
    if int(n) > 0:
        faults.inject("device-oom", times=int(n))


def _take_injection() -> bool:
    return faults.take("device-oom")


def is_oom(e: BaseException) -> bool:
    if isinstance(e, InjectedOOM):
        return True
    if type(e).__name__ != "XlaRuntimeError" and not isinstance(
            e, (RuntimeError, MemoryError)):
        return False
    msg = str(e)
    return any(m in msg for m in _OOM_MARKERS)


def relieve(frac: float = 0.5) -> int:
    """Pressure-relief sweep: shed ``frac`` of the ledger-accounted
    resident bytes across every client, then collect so the freed
    device buffers actually return to the allocator."""
    from pilosa_tpu import memory
    need = memory.ledger().reclaim_frac(frac, trigger="oom")
    gc.collect()
    return need


def guarded(run, host_fallback=None):
    """Run a device dispatch under the OOM recovery ladder (see module
    docstring).  ``host_fallback`` is the degraded-mode closure; None
    means re-raise after the bounded retry."""
    def attempt():
        # the injection seam fails attempts AND retries, so tests/CI
        # can drive every rung of the ladder (inject_oom(1) = absorbed
        # by the retry; inject_oom(2) = degraded host fallback)
        if _take_injection():
            raise InjectedOOM(
                "RESOURCE_EXHAUSTED: injected by "
                "pilosa_tpu.memory.pressure.inject_oom")
        return run()
    try:
        return attempt()
    except Exception as e:
        if not is_oom(e):
            raise
        metrics.OOM_TOTAL.inc(outcome="caught")
        # incident trigger (obs/incidents.py): an OOM-ladder trip is
        # exactly the moment whose residency/flight state an operator
        # needs later — capture one rate-limited bundle off this
        # thread before the relief sweep mutates the evidence
        from pilosa_tpu.obs import incidents
        incidents.report("device-oom", detail=type(e).__name__,
                         context={"message": str(e)[:300]})
        relieve()
        if OOM_RETRY:
            try:
                out = attempt()
                metrics.OOM_TOTAL.inc(outcome="retry_ok")
                return out
            except Exception as e2:
                if not is_oom(e2):
                    raise
        if host_fallback is not None and HOST_FALLBACK:
            _warn_degraded()
            metrics.OOM_TOTAL.inc(outcome="host_fallback")
            return host_fallback()
        metrics.OOM_TOTAL.inc(outcome="raised")
        raise


def _warn_degraded():
    global _warned_degraded
    if not _warned_degraded:
        _warned_degraded = True
        import logging
        logging.getLogger("pilosa_tpu.memory").warning(
            "device RESOURCE_EXHAUSTED persisted after eviction + "
            "retry; serving this query from the host engine "
            "(degraded mode)")


def run_host_plan(plan, leaves, params):
    """Degraded-mode execution: the SAME stacked plan, jitted onto the
    host CPU backend with the leaves fetched to numpy.  Bit-exact with
    the device program by construction; Pallas kernels stay off (the
    XLA reference paths serve every plan kind)."""
    import numpy as np
    import jax

    from pilosa_tpu.executor import stacked

    cpu = jax.local_devices(backend="cpu")[0]
    lv = tuple(np.asarray(x) for x in leaves)
    pv = tuple(np.asarray(x) for x in params)
    with jax.default_device(cpu):
        fn = jax.jit(stacked._plan_run(plan))
        return jax.block_until_ready(fn(lv, pv))
