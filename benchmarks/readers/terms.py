"""Terms a metric definition can name, shared by the arithmetic readers.

A term is a one-key object:
  {"scalar": "items"}            a number the job reported
  {"counter": "executor.h2d_bytes"}    a telemetry counter's growth in the window
  {"hist_sum": "io.consumer_wait_seconds"}   a telemetry histogram's added sum
"""


def value(window, term):
    (kind, arg), = term.items()
    if kind == "scalar":
        return window.scalars.get(arg)
    if window.before is None or window.after is None:
        return None
    if kind == "counter":
        return window.counter_delta(arg)
    if kind == "hist_sum":
        return window.hist_delta(arg)[1]
    raise ValueError("unknown term kind %r" % kind)


def product(window, terms):
    out = 1.0
    for t in terms:
        v = value(window, t)
        if v is None:
            return None
        out *= v
    return out
