"""Profiling hooks: attributable op timings, sub-spans, transfer and compile
counters, and an optional jax.profiler gate.

Kernel ops (:mod:`repro.kernels.select_topk.ops`,
:mod:`repro.kernels.fleet_state.ops`) and the executors can't see which
server (if any) is observing them, so everything here routes through one
module global: a server whose recorder is enabled registers it with
:func:`set_profiler` (a server built with observability off clears it), and

* :func:`timed_call` becomes a timed, ``jax.block_until_ready``-fenced
  call feeding :meth:`~repro.obs.recorder.RunRecorder.record_op`, inside a
  span of the op's name;
* :func:`span` returns the active recorder's span, so code below the
  server (the executors' ``train.*`` stages) nests sub-spans under the
  server's phases;
* :func:`count_h2d`, :func:`count_implicit_h2d` and :func:`count_d2h` add
  the bytes that cross the host link, and the blocking reads, to the
  recorder's ``h2d_bytes.<layer>`` / ``d2h_bytes.<layer>`` /
  ``host_syncs.<layer>`` counters; :func:`count_resident_upload` counts
  the one-time upload of the device-resident training rows
  (``h2d_bytes.resident``, ``resident_uploads``);
* one ``jax.monitoring`` listener, registered once per process, adds 1 to
  ``compiles.<span path>`` per XLA compile (or persistent-cache load).

With no active profiler (the default) each of these is one ``is None``
check: no timing, no device sync, no byte counting — so un-observed runs
pay nothing and async dispatch keeps overlapping host work (the fence only
exists while someone is measuring).

:func:`trace_gate` wraps a block in ``jax.profiler.trace`` when a trace
directory is supplied (argument or ``REPRO_JAX_TRACE`` env var), for
XLA-level drill-down past the span layer; the recorder's spans appear
there as ``fl:<name>`` annotations.
"""
from __future__ import annotations

import os
import time
from contextlib import contextmanager
from typing import Optional

import jax

from repro.obs.recorder import NULL_RECORDER

_ACTIVE = None
_LISTENING = False

# JAX records this event once per program, around the compile or the
# persistent-cache load alike
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def set_profiler(recorder) -> None:
    """Make ``recorder`` the destination for timings, sub-spans and
    counters (and register the compile listener on first use)."""
    global _ACTIVE, _LISTENING
    _ACTIVE = recorder
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_compile)
        _LISTENING = True


def clear_profiler(recorder=None) -> None:
    """Deactivate profiling (pass the recorder to clear only if it is
    still the active one — lets servers clean up without clobbering a
    newer registration)."""
    global _ACTIVE
    if recorder is None or _ACTIVE is recorder:
        _ACTIVE = None


def active_profiler():
    return _ACTIVE


def span(name: str):
    """The active recorder's span ``name``, or the shared null span."""
    prof = _ACTIVE
    if prof is None:
        return NULL_RECORDER.span(name)
    return prof.span(name)


def timed_call(name: str, fn, *args, **kwargs):
    """Call ``fn(*args, **kwargs)``; when a profiler is active, open span
    ``name``, fence the result with ``jax.block_until_ready`` (so device
    work is charged to the op that launched it, not the next host sync)
    and record the wall-clock under ``name`` in the op table."""
    prof = _ACTIVE
    if prof is None:
        return fn(*args, **kwargs)
    with prof.span(name):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        out = jax.block_until_ready(out)
        prof.record_op(name, time.perf_counter() - t0)
    return out


# -- transfer counters ----------------------------------------------------
def _leaves(trees):
    return jax.tree.leaves(list(trees))


def _is_device(leaf) -> bool:
    return isinstance(leaf, jax.Array)


def _tree_nbytes(leaves) -> int:
    return sum(int(getattr(leaf, "nbytes", 0)) for leaf in leaves)


def count_h2d(layer: str, *trees) -> None:
    """Explicit upload: every array leaf of ``trees`` (what ``jnp.asarray``
    or ``device_put`` just returned) crossed host→device."""
    prof = _ACTIVE
    if prof is None:
        return
    prof.metrics.count(f"h2d_bytes.{layer}", _tree_nbytes(_leaves(trees)))


def count_implicit_h2d(layer: str, *operands) -> None:
    """Implicit upload: ``operands`` are every operand of one jitted or
    eager ``jnp`` computation.  Its NumPy leaves cross host→device when any
    operand is on the device (an expression of NumPy operands alone stays
    on the host); its device leaves are already there."""
    prof = _ACTIVE
    if prof is None:
        return
    leaves = _leaves(operands)
    if any(_is_device(leaf) for leaf in leaves):
        host = [leaf for leaf in leaves if not _is_device(leaf)]
        prof.metrics.count(f"h2d_bytes.{layer}", _tree_nbytes(host))


def count_resident_upload(*trees) -> None:
    """One-time upload of data that stays on the device (the training
    rows): its bytes under ``h2d_bytes.resident`` and 1 under
    ``resident_uploads``."""
    prof = _ACTIVE
    if prof is None:
        return
    prof.metrics.count("h2d_bytes.resident", _tree_nbytes(_leaves(trees)))
    prof.metrics.count("resident_uploads")


def count_d2h(layer: str, *trees) -> None:
    """Pull to host (``np.asarray``, ``float``): the device leaves of
    ``trees`` cross device→host, one blocking read each; host leaves cost
    nothing."""
    prof = _ACTIVE
    if prof is None:
        return
    dev = [leaf for leaf in _leaves(trees) if _is_device(leaf)]
    prof.metrics.count(f"d2h_bytes.{layer}", _tree_nbytes(dev))
    prof.metrics.count(f"host_syncs.{layer}", len(dev))


# -- compile counter --------------------------------------------------------
def _on_compile(event: str, duration: float, **_) -> None:
    prof = _ACTIVE
    if prof is None or event != COMPILE_EVENT:
        return
    path = prof.span_path()
    prof.metrics.count(f"compiles.{path}" if path else "compiles")


@contextmanager
def trace_gate(out_dir: Optional[str] = None):
    """Optionally wrap a block in a ``jax.profiler`` trace.  Active when
    ``out_dir`` is given or ``REPRO_JAX_TRACE`` names a directory; a no-op
    otherwise."""
    target = out_dir or os.environ.get("REPRO_JAX_TRACE")
    if not target:
        yield
        return
    with jax.profiler.trace(target):
        yield
