"""Span-based tracing with Chrome trace-event export (ISSUE 8).

``Tracer.span`` wraps a phase of the sweep/decision pipeline — spec
packing, device compile+dispatch, per-chunk ``simulate_packed``, cache
get/put/re-bill, refinement rounds — in a context manager that records a
complete-duration event. ``dump`` writes the Chrome trace-event JSON
format, loadable in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing``; every event carries the tracer's ``run_id`` in its
``args`` so traces from multiple runs correlate.

The tracer is **disabled by default**: an idle span is one attribute
check and a no-op context manager, so library code can wrap hot phases
unconditionally. The CLIs enable it when ``--trace-out`` is given.

An enabled tracer also opens a ``jax.profiler.TraceAnnotation`` of the
span's name around each span (where jax imports; it is imported at
``enable``, never at module import). Under a JAX profiler session the
span then lands on the trace's host plane, on the profiler's own clock,
beside the device's operations.

``jax_device_profile`` is the optional deep-dive hook: when tracing is
enabled and jax is importable it brackets the block with
``jax.profiler.start_trace``/``stop_trace`` (TensorBoard/XProf format,
per-HLO timing on the compiled path); otherwise it is a no-op, so the
module stays importable — and every caller runnable — without jax.

The span → call-site map lives in ``docs/observability.md``
("Trace-span map"). Spans are parent-process only: worker processes
(pool or fleet) ship metrics deltas back, not spans.
"""

from __future__ import annotations

import json
import os
import threading
import time
import uuid
from contextlib import contextmanager, nullcontext
from typing import Any, Callable, Dict, Iterable, List, Optional


def _profiler_annotation() -> Optional[Callable]:
    """``jax.profiler.TraceAnnotation``, or ``None`` where jax does not
    import."""
    try:
        from jax.profiler import TraceAnnotation
    except Exception:
        return None
    return TraceAnnotation


class Tracer:
    """Process-local span recorder (Chrome trace-event JSON).

    Spans nest naturally per thread — the Chrome format reconstructs the
    flame graph from (tid, ts, dur) of complete ("ph": "X") events, so
    no explicit parent bookkeeping is needed.
    """

    def __init__(self, run_id: Optional[str] = None, enabled: bool = False):
        self.enabled = enabled
        self.run_id = run_id or uuid.uuid4().hex[:8]
        self._annotation = _profiler_annotation() if enabled else None
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    # -- switches -----------------------------------------------------------
    def enable(self, run_id: Optional[str] = None) -> None:
        if run_id is not None:
            self.run_id = run_id
        self._annotation = _profiler_annotation()
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self._events.clear()

    # -- recording ----------------------------------------------------------
    @contextmanager
    def span(self, name: str, **args: Any):
        """Record a complete-duration event around the wrapped block.

        ``args`` become the event's ``args`` payload (JSON-safe values
        only; non-serializable values are ``repr``-ed at dump time).
        Exceptions propagate; the span still closes and is annotated
        with ``error=True``. The block also runs inside a profiler
        annotation of the same name (module docstring).
        """
        if not self.enabled:
            yield
            return
        annotation = self._annotation
        t0 = time.perf_counter_ns()
        try:
            with annotation(name) if annotation else nullcontext():
                yield
        except BaseException:
            args = dict(args, error=True)
            raise
        finally:
            t1 = time.perf_counter_ns()
            self._append({
                "name": name, "ph": "X", "cat": "repro",
                "ts": t0 // 1000, "dur": max((t1 - t0) // 1000, 1),
                "pid": os.getpid(), "tid": threading.get_ident(),
                "args": {**args, "run_id": self.run_id},
            })

    def instant(self, name: str, **args: Any) -> None:
        """Record a zero-duration marker event."""
        if not self.enabled:
            return
        self._append({
            "name": name, "ph": "i", "s": "p", "cat": "repro",
            "ts": time.perf_counter_ns() // 1000,
            "pid": os.getpid(), "tid": threading.get_ident(),
            "args": {**args, "run_id": self.run_id},
        })

    def _append(self, event: Dict[str, Any]) -> None:
        with self._lock:
            self._events.append(event)

    # -- export -------------------------------------------------------------
    @property
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def to_chrome_dict(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON document (Perfetto-loadable)."""
        return {
            "traceEvents": self.events,
            "displayTimeUnit": "ms",
            "otherData": {"run_id": self.run_id,
                          "exported_unix": time.time()},
        }

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_dict(), f, indent=1, default=repr)


#: Process-global tracer: disabled until a CLI (or test) enables it.
_TRACER = Tracer()


def get_tracer() -> Tracer:
    """The process-global :class:`Tracer`."""
    return _TRACER


@contextmanager
def jax_device_profile(logdir: Optional[str]):
    """Optional ``jax.profiler`` bracket for the compiled path.

    Active only when ``logdir`` is set, the global tracer is enabled,
    and jax imports cleanly — every other combination is a silent no-op
    so callers never need to gate on jax availability.
    """
    if not logdir or not _TRACER.enabled:
        yield
        return
    try:
        import jax
    except Exception:
        yield
        return
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


__all__: Iterable[str] = ["Tracer", "get_tracer", "jax_device_profile"]
