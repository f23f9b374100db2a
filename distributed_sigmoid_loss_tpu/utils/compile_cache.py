"""Where the persistent XLA compilation cache lives, what keys it, and what the
compiler did in this process — the one place that says.

A cold B/16 train step costs minutes of compile; every entry point (``cli.main``,
``chip_smoke.py``, ``__graft_entry__.py``, the test bootstrap, the
benchmark's harness) calls :func:`configure_compile_cache` so repeated runs hit disk
instead. The directory must be placeable from outside (a chip machine may mount its
own), hence the rule:

- ``JAX_COMPILATION_CACHE_DIR`` set (even to ``""``, which disables the cache):
  the directory is left alone — jax reads the variable itself.
- otherwise: ``<checkout>/.jax_cache``, a fixed git-ignored path, so a second
  process started from the same checkout finds the first one's programs.

Either way the key includes the program's metadata
(``jax_compilation_cache_include_metadata_in_key``). By default jax hashes the
module after ``strip-debuginfo`` (jax/_src/cache_key.py), and the names a
profile reads — the ``jax.named_scope`` paths of train/train_step.py — live in
exactly that debug info: two programs that differ only by a scope share a key,
and the cache hands the second one the first one's executable, whose profile
carries the old names or none. With the metadata in the key a stale name is a
miss instead. The price: the key also holds source files and lines, so an edit
that moves a line of any function on the traced path costs that program one
cold compile; a checkout that does not change keeps hitting. File names are
written relative to the checkout (``jax_hlo_source_file_canonicalization_regex``
strips its path), so two checkouts that share a cache directory (a parent and a
change side by side, an unpacked archive) share every program whose traced
source is the same in both. **On the driver's machine the directory is capped
near 192 MiB**, and one side's B/16 programs are about 150 MB: a parent and a
change whose programs differ in key do not both fit, each evicts the other, and
both sides then run cold in turn. That is why a moved line costs both sides,
and why the start-up spans wrap traced functions at a file's end
(``obs/spans.py spanned``) with ``obs/spans.py``'s own frames kept out of the
programs' locations (below).

**The compile log.** :func:`configure_compile_cache` also registers, once a
process however often it is called, listeners on ``jax.monitoring`` that write
into the process's recorder (``obs/spans.py RECORDER``):

- one span for every trace (``jax.trace``), lowering to StableHLO (``jax.lower``)
  and backend compile (``jax.backend_compile``) that jax reports, with the
  program's name as ``attrs["fun_name"]``, on the thread that compiled, so a
  span of the program's own (``init_state``) holds them by containment; of
  traces inside a trace (every jitted function a step calls) the outermost;
- on each backend-compile span ``attrs["cache"]``: ``hit`` (read from the
  persistent cache and loaded; ``retrieval_s`` is jax's time for the read),
  ``miss`` (asked the cache, then compiled) or ``off`` (the cache was not
  asked: disabled, or a backend it does not serve);
- the counters ``compile_cache.requests``, ``.hits`` and ``.misses``
  (requests = hits + misses). jax stores no entry for a program that compiled
  in under a second, so small programs miss on every run by design: the spans'
  names and seconds tell those from an eviction.

Nothing fires unless jax compiles, so a steady loop pays nothing; a compile
after set-up has a name and a time in the record. Each call's
``startup.compile_cache`` span carries the directory and its bytes and entries
as the call found it (the first: as the process found it);
:func:`cache_dir_stats` gives the same reading later (what a run added, what a
cap evicted).
"""

from __future__ import annotations

import functools
import os
import re
import threading

from distributed_sigmoid_loss_tpu.obs import spans

__all__ = ["CACHE_ENV", "cache_dir_stats", "default_cache_dir", "configure_compile_cache"]

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# jax's names (jax/_src/dispatch.py, compiler.py) and the record's.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_SPAN_OF_EVENT = {
    _TRACE_EVENT: "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.backend_compile",
}
_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def default_cache_dir() -> str:
    return os.path.join(_CHECKOUT, ".jax_cache")


def cache_dir_stats(path: str) -> dict:
    """``{"bytes", "entries"}`` of a cache directory as it stands: every file's
    size, and jax's ``<key>-cache`` files counted. ``""`` or a directory that
    is not there yet reads zeros."""
    total = entries = 0
    try:
        with os.scandir(path) as it:
            for entry in it:
                if entry.is_file():
                    total += entry.stat().st_size
                    entries += entry.name.endswith("-cache")
    except OSError:
        pass
    return {"bytes": total, "entries": entries}


class _CompileLog:
    """jax's compile events, written into the process's recorder. What the
    cache said of a compile arrives before the compile's span closes, on the
    compiling thread, so it waits in a thread-local until the span takes it.
    Tracing a step traces every jitted function it calls, tens of thousands of
    events inside the step's own trace (each costs these listeners a call and a
    comparison): jax announces each start too, so a trace is kept only when it
    is the outermost on its thread. Lowerings and compiles do not nest."""

    def __init__(self):
        self._thread = threading.local()

    def on_start(self, event: str, start: float, **_) -> None:
        if event == _TRACE_EVENT:
            state = vars(self._thread)
            state["traces_open"] = state.get("traces_open", 0) + 1

    def on_event(self, event: str, **_) -> None:
        if event == _REQUEST_EVENT:
            self._thread.cache = "miss"  # until the cache says otherwise
            spans.RECORDER.count("compile_cache.requests")
        elif event == _HIT_EVENT:
            self._thread.cache = "hit"
            spans.RECORDER.count("compile_cache.hits")

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event == _RETRIEVAL_EVENT:
            self._thread.retrieval_s = duration

    def on_time_span(self, event: str, start: float, end: float, fun_name: str = "", **_) -> None:
        name = _SPAN_OF_EVENT.get(event)
        if name is None:
            return
        state = vars(self._thread)
        if event == _TRACE_EVENT:
            state["traces_open"] = still_open = state.get("traces_open", 1) - 1
            if still_open > 0:
                return  # inside another trace: the common case, so nothing is built before it
        attrs = {"fun_name": fun_name}
        if name == "jax.backend_compile":
            attrs["cache"] = state.pop("cache", "off")
            if "retrieval_s" in state:
                attrs["retrieval_s"] = state.pop("retrieval_s")
            if attrs["cache"] == "miss":
                spans.RECORDER.count("compile_cache.misses")
        spans.RECORDER.record(
            name, spans.from_wall_clock(start), spans.from_wall_clock(end), attrs=attrs
        )


_LOG = _CompileLog()


@functools.cache  # once a process, however often the cache is configured
def _register_compile_log() -> None:
    """The listeners, and ``obs/spans.py`` out of the frames jax writes into a
    program's locations (as flax does for its own wrappers), so ``spanned``
    around a function that compiles moves no cache key."""
    import jax.monitoring
    from jax._src import source_info_util

    jax.monitoring.register_scalar_listener(_LOG.on_start)
    jax.monitoring.register_event_listener(_LOG.on_event)
    jax.monitoring.register_event_duration_secs_listener(_LOG.on_duration)
    jax.monitoring.register_event_time_span_listener(_LOG.on_time_span)
    source_info_util.register_exclusion(spans.__file__)


def configure_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in use ("" = disabled)."""
    attrs: dict = {}  # filled below: the span is recorded when it closes
    with spans.startup_span("startup.compile_cache", attrs):
        import jax

        _register_compile_log()
        jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
        jax.config.update(
            "jax_hlo_source_file_canonicalization_regex", "^" + re.escape(_CHECKOUT + os.sep)
        )
        if CACHE_ENV in os.environ:
            path = os.environ[CACHE_ENV]
        else:
            path = default_cache_dir()
            jax.config.update("jax_compilation_cache_dir", path)
        attrs.update(dir=path, **cache_dir_stats(path))
    return path
