"""Device-timeline profiling — the TPU-native role of the reference's
low-level (libunwind) profiler merge (reference profile.py:550
``ll_get_stack`` / ``watch`` low-level branch, enabled by
``distributed.worker.profile.low-level``).

The reference's statistical profiler cannot see inside C frames that
release the GIL, so it merges a libunwind C-stack sampler under the
owning python frames.  On a TPU framework the invisible time is DEVICE
time: XLA executions are dispatched asynchronously and the python stack
only ever shows the dispatch call.  The TPU-native analog is the XLA
runtime profiler — ``jax.profiler.start_trace``/``stop_trace`` capture
a device timeline (TensorBoard/XProf ``plugins/profile`` format), and
while a profiler trace is active (started here or by anything else in
the process) every task the worker executes runs under a
``jax.profiler.TraceAnnotation`` carrying its key, so device ops group
under the task that launched them.  Same contract as the reference's
merge: foreign (non-python) activity is attributed to the python-level
owner, here by task key instead of by stack address.

Worker surface: the ``device_profile`` RPC (start/stop); client surface
``Client.device_profile_start/stop``.  The captured artifact is a trace
directory per worker, inspectable offline with TensorBoard's profile
plugin or XProf; this module deliberately does not parse it (the trace
format is a moving target and the viewers are the product there).

The XLA profiler is PROCESS-global (like ``memtrace``): with an
in-process LocalCluster, start the trace from a single worker — the
second start in the same process returns an error status instead of
wedging the runtime.  On real deployments (one worker process per
host/chip) the broadcast maps one trace to one process naturally.
"""

from __future__ import annotations

import contextlib
import glob
import os
import tempfile
import threading

from distributed_tpu.tracing import TraceSpan, trace_active

_lock = threading.Lock()
_active_dir: str | None = None
#: what :func:`annotate` hands out with no trace active: one shared
#: object, so the worker's per-task path allocates nothing
_NO_SPAN = contextlib.nullcontext()


def available() -> bool:
    """True when the jax profiler can run in this process."""
    try:
        import jax.profiler  # noqa: F401

        return True
    except Exception:  # pragma: no cover - jax is baked into this image
        return False


def start(logdir: str | None = None) -> dict:
    """Begin a device trace; one at a time per process.

    Returns ``{"status": "OK", "logdir": ...}`` or an ``error`` status
    when a trace is already running / jax is unavailable.
    """
    global _active_dir
    if not available():
        return {"status": "error", "error": "jax profiler unavailable"}
    with _lock:
        if _active_dir is not None:
            return {
                "status": "error",
                "error": f"device trace already active in {_active_dir}",
            }
        import jax

        logdir = logdir or tempfile.mkdtemp(prefix="dtpu-device-trace-")
        try:
            jax.profiler.start_trace(logdir)
        except Exception as exc:
            return {"status": "error", "error": repr(exc)}
        _active_dir = logdir
    return {"status": "OK", "logdir": logdir}


def stop() -> dict:
    """End the device trace; reports the artifact files captured."""
    global _active_dir
    with _lock:
        if _active_dir is None:
            return {"status": "error", "error": "no device trace active"}
        import jax

        logdir = _active_dir
        try:
            jax.profiler.stop_trace()
        except Exception as exc:
            return {"status": "error", "error": repr(exc), "logdir": logdir}
        finally:
            _active_dir = None
    files = sorted(
        os.path.relpath(p, logdir)
        for p in glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
        if os.path.isfile(p)
    )
    return {"status": "OK", "logdir": logdir, "files": files}


def annotate(key) -> contextlib.AbstractContextManager:
    """Context manager marking one task's execution on the device
    timeline of any active profiler trace; with none active it costs
    one ``TraceAnnotation.is_enabled()`` (``tracing.trace_active``) and
    returns a shared no-op."""
    if not trace_active():
        return _NO_SPAN
    return TraceSpan(str(key))
