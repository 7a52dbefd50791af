"""One-round-deferred metric materialization.

Converting a device scalar to a python float blocks the host on the
accelerator, and while the host waits nothing new is dispatched. Both
round-loop drivers (``FedAlgorithm.run`` and the CLI runner) therefore hold each
round's record as device values and materialize+log it only after the
NEXT round's programs are dispatched — same values, same cadence, the
device queue stays full.
"""
from __future__ import annotations

import subprocess
import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from ..obs import trace as obs_trace


def git_sha(repo_root: Optional[str] = None) -> str:
    """Current commit SHA ('' when git is unavailable — catalog entries
    stay useful without it)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo_root or None,
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() if out.returncode == 0 else ""
    except Exception:
        return ""


def to_float(v):
    """0-d device/numpy arrays -> float; python ints/strings/etc. pass
    through untouched (record keys like ``round`` stay ints)."""
    if isinstance(v, (jax.Array, np.ndarray)) and np.ndim(v) == 0:
        return float(v)
    return v


class DeferredRecords:
    """Holds at most one pending record; ``push`` flushes the previous one.

    ``timed=True`` stamps ``round_time_s`` at flush boundaries (the time
    since the previous flush), so the SUM over a run equals wall time
    exactly and per-round attribution is ±1 round — the honest semantics
    under deferred fetching, where the blocking conversion itself happens
    between rounds. Call ``flush`` in a ``finally`` so a crash in round r
    still emits round r-1's already-computed metrics (best-effort: the
    pending fetch may itself raise if the device is gone).
    """

    def __init__(self, log: Callable[[Dict[str, Any]], None],
                 timed: bool = False):
        self._log = log
        self._timed = timed
        self._pending: Optional[Dict[str, Any]] = None
        self._last_t = time.perf_counter()

    def push(self, record: Dict[str, Any]) -> None:
        self.flush()
        self._pending = record

    def flush(self) -> None:
        rec, self._pending = self._pending, None
        if rec is None:
            return
        # the round driver's one blocking fetch: the host waits here for
        # the device to finish the record's round
        with obs_trace.span("flush", {"round": rec.get("round")}):
            for k, v in rec.items():
                rec[k] = to_float(v)
        if self._timed:
            t = time.perf_counter()
            rec["round_time_s"] = t - self._last_t
            self._last_t = t
        self._log(rec)

    def flush_safely(self) -> None:
        """``flush`` for exception paths: swallow a fetch that dies with
        the device so the original error propagates instead."""
        try:
            self.flush()
        except Exception:  # pragma: no cover - device-loss path
            self._pending = None


class RunCounters:
    """Run-level fault/recovery totals, accumulated from per-round records.

    The fault-tolerance subsystem (robust/faults.py, robust/guard.py)
    emits its per-round counters as ordinary float record fields
    (``clients_dropped``, ``clients_quarantined``); both round-loop
    drivers feed records through :meth:`update` — including attempts the
    watchdog rolled back, so totals cover every fault that occurred —
    and :meth:`summary` lands in stat_info as ``fault_recovery``
    (alongside the watchdog's own ``rounds_retried``/``rounds_skipped``
    totals, which are authoritative for retry accounting). Values may
    still be device scalars when a record is pushed (DeferredRecords
    materializes late) — ``to_float`` handles both."""

    FIELDS = ("clients_dropped", "clients_quarantined")

    def __init__(self, registry=None) -> None:
        """``registry`` (an ``obs.metrics.MetricsRegistry``) mirrors each
        accumulated field into a ``fault_<field>_total`` counter — the
        obs absorption path; None (the default) keeps the standalone
        behavior the robust layer has always had."""
        self._totals: Dict[str, float] = {}
        self._registry = registry

    def update(self, record: Dict[str, Any]) -> None:
        for field in self.FIELDS:
            v = record.get(field)
            if v is not None:
                fv = float(to_float(v))
                self._totals[field] = self._totals.get(field, 0.0) + fv
                if self._registry is not None and fv:
                    self._registry.counter(
                        "fault_" + field + "_total").inc(fv)

    def summary(self) -> Dict[str, float]:
        return dict(self._totals)
