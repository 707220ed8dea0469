"""The device a run is on: required, described, and its compile clock."""
import sys
import threading


def require_accelerator(chips):
    """Exit non-zero, printing no result, unless JAX's default backend
    is an accelerator with at least `chips` devices.  Nothing falls back
    to the CPU: a CPU timing under a device metric's name is worse than
    no number."""
    import jax

    backend = jax.default_backend()
    if backend == "cpu":
        sys.exit("benchmark: JAX's default backend is the CPU (devices: %s);"
                 " nothing was run" % (jax.devices(),))
    if jax.device_count() < chips:
        sys.exit("benchmark: the cell needs %d chip(s), JAX sees %d; "
                 "nothing was run" % (chips, jax.device_count()))
    return jax.devices()[:chips]


def describe(used):
    """The `device` object of the result line, as JAX reports it.
    `memory_peak_bytes` is the peak on the fullest chip used: the
    runtime's `peak_bytes_in_use` (buffers: weights, staged inputs, KV
    rings) plus its `peak_bytes_reserved` (what running programs held
    for their temporaries — 5.9 GB of activations for a ResNet-50 step
    at batch 256, which `peak_bytes_in_use` leaves out; seen on the v5e,
    PR 22)."""
    import jax

    peak = 0
    for d in used:
        stats = d.memory_stats()  # None on XLA:CPU
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                       + int(stats.get("peak_bytes_reserved", 0)))
    first = used[0]
    return {"platform": first.platform, "kind": first.device_kind,
            "count": jax.device_count(), "memory_peak_bytes": peak}


class CompileClock:
    """Seconds and count of XLA backend compiles (or persistent-cache
    retrievals), from JAX's own monitoring events; jit calls and the
    program's AOT lower().compile() both report here."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self._lock = threading.Lock()
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENT:
            with self._lock:
                self.seconds += duration
                self.count += 1

    def read(self):
        with self._lock:
            return self.seconds, self.count
