"""What a job hands the metric readers after its measured window."""


class Window:
    """`scalars`: name -> number (items, seconds, setup_s, ...);
    `series`: name -> list of samples (ttft_ms, itl_ms, ...);
    `before` / `after`: telemetry snapshots at the window's ends;
    `trace`: the trace reduction (traced runs on a chip) or None;
    `device`: the result line's device object; `peaks`: the device's
    row of the peak table (None off the chip); `compile`: (seconds,
    count) of backend compiles during set-up."""

    def __init__(self):
        self.scalars = {}
        self.series = {}
        self.before = None
        self.after = None
        self.trace = None
        self.device = None
        self.peaks = None
        self.compile = (0.0, 0)
        self.attempted = 0
        self.failed = 0
        self.correct = False
        self.notes = {}

    def counter_delta(self, name):
        return (self.after["counters"].get(name, 0)
                - self.before["counters"].get(name, 0))

    def hist_delta(self, name):
        """(count, sum) added to a telemetry histogram in the window."""
        a = self.after["histograms"].get(name, (0, 0.0))
        b = self.before["histograms"].get(name, (0, 0.0))
        return a[0] - b[0], a[1] - b[1]


def telemetry_snapshot():
    """Counters, and (count, sum) per histogram — the two things the
    program's telemetry reports soundly (its bucket quantiles and
    last-value gauges are not read)."""
    from mxnet_tpu import telemetry

    snap = telemetry.snapshot()
    return {"counters": snap["counters"],
            "histograms": {k: (h["count"], h["sum"])
                           for k, h in snap["histograms"].items()
                           if "count" in h and "sum" in h}}
