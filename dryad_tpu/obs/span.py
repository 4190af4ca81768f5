"""Hierarchical spans over the ``EventLog`` stream.

A span measures one timed region (job, stage attempt, vertex attempt,
chunk, pipeline phase) on monotonic clocks and serializes into the
existing event stream as ONE ``span`` event at close:

``{"kind": "span", "name", "cat", "span_id", "parent_id", "dur",
"thread", ...fields}``

plus the stamps ``EventLog.emit`` adds (``ts`` wall-clock at close,
``mono``).  The span's start is recoverable as ``ts - dur`` /
``mono - dur`` — no separate begin event, so a span costs one log
record and the stream can never hold an unmatched begin.

Parenting is implicit per thread (a thread-local stack), so nested
``with`` blocks form the job -> stage -> chunk hierarchy without
plumbing ids; a pipeline thread that logically works FOR a driver-side
span passes ``parent=`` explicitly (capture it with
:meth:`Tracer.current_id` before handing work to the thread).

Span ids are unique process-wide (one shared counter), so any module
may construct its own ``Tracer(events)`` over the same log and the
hierarchy stays consistent.  The stack of open spans is a tracer's own:
a module whose spans must nest under another's is handed that tracer
(the telemetry sampler the context's, so ``resource_sample`` is a child
of the span whose event found the sample due).  So are the fields of
:meth:`Tracer.stamped`: what a layer knows about the work below it (which
of a job's answers a fetch is) goes onto every span that this thread
opens through the tracer meanwhile, and the layers below take no
parameter for it.

Every span is also a ``jax.profiler.TraceAnnotation`` over the same
interval, named ``dryad:<phase>:<name>`` (``<phase>`` from
:func:`dryad_tpu.obs.critpath.phase_of`) with ``span_id``,
``parent_id``, ``qid`` and the numeric fields it was opened with as
stats: in a profiler session (``config.profile_dir``, the benchmark's
traced run) the program's spans lie on the device trace's clock.  With
no session the annotation is a flag check.  What ``add()`` attaches
before the close, and what the close itself learns, rides the same
annotation (``set_metadata``, in a session only): a numeric field that
is new, or whose value is no longer the one sent at open, is sent once
more, and a reader that takes a stat's last value reads the event's.

**A span that accounts for itself** (``account=True``, the host passes
that write a table's worth of host memory: the encodes, ``pack``,
``tokenize``, ``vocab``, ``fetch_copy``, ``decode``, ``unpack``) takes
``resource.getrusage(RUSAGE_SELF)`` at open and at close and adds
``user_s`` / ``sys_s``: CPU seconds of the whole process in the
interval, the pass's own thread, its worker threads and the runtime's
copy threads alike.  Against ``dur`` they say whether one core was busy,
many were, or the thread waited.  Inclusive of its children, like
``dur``.  Such a pass also states ``bytes_out`` itself: the bytes of
the arrays it made (``fetch_copy`` says them under ``bytes``).  Page
faults are not taken: the chip's host (gVisor) counts none.
"""

from __future__ import annotations

import contextlib
import itertools
import resource
import threading
import time
from typing import Any, Optional

from jax.profiler import TraceAnnotation

from dryad_tpu.obs import tracectx
from dryad_tpu.obs.critpath import phase_of

__all__ = ["Span", "Tracer", "UNTRACED"]

# process-wide id source: tracers are cheap per-module conveniences,
# so ids must not collide across instances
_ids = itertools.count(1)
_ids_lock = threading.Lock()


def _next_id() -> int:
    with _ids_lock:
        return next(_ids)


_UNSET = object()


class Span:
    """One open timed region; emits its ``span`` event at ``__exit__``.

    ``add(**fields)`` attaches result facts discovered mid-region
    (rows, bytes, bucket ids) to the closing event.
    """

    __slots__ = (
        "_tracer", "name", "cat", "fields", "span_id", "parent_id", "_t0",
        "_annotation", "_sent", "_account", "_usage",
    )

    def __init__(self, tracer, name, cat, parent_id, fields, account=False):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.fields = fields
        self.span_id = _next_id()
        self.parent_id = parent_id
        self._t0 = 0.0
        self._annotation = None
        self._sent = None  # the stats the annotation was opened with
        self._account = account
        self._usage = None  # getrusage at open, of an accounted span

    def add(self, **fields: Any) -> "Span":
        self.fields.update(fields)
        return self

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        stats = self._sent = {
            k: v for k, v in self.fields.items()
            if isinstance(v, (int, float))
        }
        qid = self.fields.get("qid") or tracectx.current_qid()
        if qid:  # the profiler keeps no empty stat
            stats["qid"] = qid
        self._annotation = TraceAnnotation(
            f"dryad:{phase_of(self.name, self.cat)}:{self.name}",
            span_id=self.span_id, parent_id=self.parent_id or 0, **stats,
        )
        self._annotation.__enter__()
        self._t0 = time.monotonic()
        if self._account:
            self._usage = resource.getrusage(resource.RUSAGE_SELF)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # the account and what goes onto the annotation lie inside the
        # interval, so the event and the annotation time the same work
        if self._usage is not None:
            was, now = self._usage, resource.getrusage(resource.RUSAGE_SELF)
            self.fields.update(
                user_s=round(now.ru_utime - was.ru_utime, 6),
                sys_s=round(now.ru_stime - was.ru_stime, 6),
            )
        if TraceAnnotation.is_enabled():
            sent = self._sent
            late = {
                k: v for k, v in self.fields.items()
                if isinstance(v, (int, float)) and sent.get(k, _UNSET) != v
            }
            if late:
                self._annotation.set_metadata(**late)
        dur = time.monotonic() - self._t0
        self._annotation.__exit__(exc_type, exc, tb)
        self._tracer._pop(self)
        if exc_type is not None and exc_type is not StopIteration:
            # StopIteration is iterator protocol, not a fault (the
            # prefetch span around a source pull ends its stream with it)
            self.fields.setdefault("error", f"{exc_type.__name__}: {exc}")
        # a field passed at construction (worker spans re-activating a
        # wire context may pre-stamp) wins over the thread-local scope
        qid = self.fields.pop("qid", None) or tracectx.current_qid()
        self._tracer._events.emit(
            "span", name=self.name, cat=self.cat, span_id=self.span_id,
            parent_id=self.parent_id, dur=round(dur, 6), qid=qid,
            thread=threading.current_thread().name, **self.fields,
        )


class _NullSpan:
    """Shared no-op span for disabled tracers."""

    __slots__ = ()
    span_id = None
    parent_id = None

    def add(self, **fields: Any) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NULL = _NullSpan()


class Tracer:
    """Span factory bound to one :class:`~dryad_tpu.exec.events.EventLog`.

    Thread-safe: each thread keeps its own open-span stack, so spans
    emitted concurrently from pipeline threads nest correctly within
    their own thread and never corrupt another thread's hierarchy.
    ``events=None`` (or ``enabled=False``) yields no-op spans with no
    allocation, so instrumented code needs no guards.
    """

    def __init__(self, events=None, enabled: bool = True):
        self._events = events
        self.enabled = enabled and events is not None
        self._local = threading.local()

    # -- per-thread stack --------------------------------------------------
    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, span: Span) -> None:
        self._stack().append(span)

    def _pop(self, span: Span) -> None:
        st = self._stack()
        if st and st[-1] is span:
            st.pop()
        elif span in st:  # mis-nested exit: drop it and everything above
            del st[st.index(span):]

    def current_id(self) -> Optional[int]:
        """Id of this thread's innermost open span (to pass as
        ``parent=`` into work handed to another thread)."""
        st = self._stack()
        return st[-1].span_id if st else None

    # -- public ------------------------------------------------------------
    def span(self, name: str, cat: str = "driver", parent=_UNSET,
             account: bool = False, **fields: Any):
        """Open a span as a context manager.  ``parent`` defaults to
        this thread's innermost open span; pass an explicit id (or
        None) when the logical parent lives on another thread.
        ``account``: the span accounts for itself (module doc)."""
        if not self.enabled:
            return _NULL
        pid = self.current_id() if parent is _UNSET else parent
        stamp = getattr(self._local, "stamp", None)
        return Span(
            self, name, cat, pid, {**stamp, **fields} if stamp else fields,
            account,
        )

    @contextlib.contextmanager
    def stamped(self, **fields: Any):
        """Every span this thread opens through the tracer inside the
        block carries ``fields`` (a span's own field of the same name
        wins)."""
        was = getattr(self._local, "stamp", None)
        self._local.stamp = {**was, **fields} if was else fields
        try:
            yield
        finally:
            self._local.stamp = was


# The default of a ``tracer=`` parameter (``columnar/batch.py``,
# ``parallel/distribute.py``): no event log, so every span is ``_NULL``.
UNTRACED = Tracer()
