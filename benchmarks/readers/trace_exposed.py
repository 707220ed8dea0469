"""Share of the traced window spent in collective ops while no other op
ran on that chip (mean over chips), in percent.  Only from a chip's
trace, and only where more than one chip was traced."""


def read(window):
    if window.trace is None or window.trace["chips_traced"] < 2:
        return None
    return (100.0 * window.trace["exposed_collective_s"]
            / window.trace["window_s"])
