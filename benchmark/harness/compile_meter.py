"""What JAX itself reports about compilation: seconds inside
``backend_compile`` (a persistent-cache hit costs only its retrieval) and
the number of programs, with the time each event fired so that compiles
inside a measured window can be counted."""
import threading
import time


class CompileMeter(object):
    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.events = []      # (perf_counter, seconds)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.events.append((time.perf_counter(), float(seconds)))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1

    def between(self, t0, t1):
        """(programs, seconds) of compiles that ended in [t0, t1]."""
        with self._lock:
            evs = [s for t, s in self.events if t0 <= t <= t1]
        return len(evs), sum(evs)
