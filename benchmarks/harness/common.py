"""Small pieces both job kinds use."""
import os
import threading
import time

from . import spec


def contexts(mx, devices):
    """The program's Context for each JAX device the cell uses."""
    make = {"tpu": mx.tpu, "cpu": mx.cpu}  # cpu: the tests' rehearsal
    return [make[d.platform](i) for i, d in enumerate(devices)]


def annotate(name):
    """A span of the benchmark's own, on the profiler's clock, so that a
    device idle gap can be named by what the benchmark was doing."""
    import jax

    return jax.profiler.TraceAnnotation("bench:" + name)


TRACE_DIR = os.path.join(spec.HERE, ".run", "trace")


class MidWindowTrace:
    """Trace `length_s` seconds in the middle of a window of `seconds`
    that starts at perf_counter time `t0`, from a helper thread, so that
    the job's own thread never waits for the profiler."""

    def __init__(self, t0, seconds, length_s, host_level=1):
        self._host_level = host_level
        self._start = t0 + max(0.0, (seconds - length_s) / 2.0)
        self._length = min(length_s, seconds)
        self.error = None
        self._thread = threading.Thread(target=self._run, name="bench_trace",
                                        daemon=True)
        self._thread.start()

    def _run(self):
        import shutil

        import jax

        try:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            time.sleep(max(0.0, self._start - time.perf_counter()))
            options = jax.profiler.ProfileOptions()
            # annotations and device ops only: every Python call, every
            # runtime TraceMe and the HLO protos make a trace of hundreds
            # of megabytes that takes minutes to write and read.  Level 1
            # keeps the benchmark's annotations; a traffic file sets 0
            # where the runtime's own level-1 events (2.5 million for
            # 1.5 s of staging 600 MB blocks) swamp them
            options.python_tracer_level = 0
            options.host_tracer_level = self._host_level
            options.enable_hlo_proto = False
            jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
            try:
                with annotate("window"):
                    time.sleep(self._length)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # reported by finish(); never kills the job
            self.error = e

    def finish(self, chips, on_chip):
        """Wait for the trace and reduce it.  Off the chip (a CPU
        rehearsal) there is no device plane and the result is None: no
        device number is made up from a CPU run."""
        import shutil

        from . import trace_reduce

        t0 = time.perf_counter()
        self._thread.join(600)
        if self._thread.is_alive():
            raise RuntimeError("the profiler did not stop within 600 s")
        if self.error is not None:
            raise self.error
        path = trace_reduce.find_xplane(TRACE_DIR)
        t1 = time.perf_counter()
        try:
            data = trace_reduce.load(path)
            for row in trace_reduce.inventory(data):
                print("[bench] trace plane %r line %r: %d events, %.4f to "
                      "%.4f s" % row, flush=True)
            reduced = (trace_reduce.reduce_trace(data, chips) if on_chip
                       else None)
            print("[bench] trace of %d bytes: waited %.1f s for the profiler,"
                  " read it in %.1f s" % (os.path.getsize(path), t1 - t0,
                                          time.perf_counter() - t1),
                  flush=True)
            return reduced
        finally:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
