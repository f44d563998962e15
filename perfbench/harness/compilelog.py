"""Count compile requests and persistent-cache hits, as
``chip_smoke.py::CacheLog`` does (PR 21): JAX names the program of a cache
lookup only in its compiler log at DEBUG, and counts every compile
request, cached or not, as a ``backend_compile_duration`` event."""
import logging

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileLog(logging.Filter):
    """A filter, so the DEBUG records read here go no further."""

    def __init__(self):
        super().__init__()
        import jax
        self.requests = 0
        self.cache_hits = 0
        self._log = logging.getLogger("jax._src.compiler")
        self._pass_level = self._log.getEffectiveLevel()
        self._old_level = self._log.level
        self._log.addFilter(self)
        self._log.setLevel(logging.DEBUG)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, _secs, **_kw):
        if event == _COMPILE_EVENT:
            self.requests += 1

    def filter(self, record):
        if str(record.msg).startswith("Persistent compilation cache hit"):
            self.cache_hits += 1
        return record.levelno >= self._pass_level

    def snapshot(self):
        return {"requests": self.requests, "cache_hits": self.cache_hits}

    def close(self):
        self._log.removeFilter(self)
        self._log.setLevel(self._old_level)
        try:
            from jax._src import monitoring
            monitoring._unregister_event_duration_listener_by_callback(
                self._on_event)
        except (ImportError, AttributeError, AssertionError):
            pass        # the listener only counts; leaving it is harmless
