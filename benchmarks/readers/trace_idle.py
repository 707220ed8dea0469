"""Share of the traced window in which no op ran on the device (mean
over the chips used), in percent.  Only from a chip's trace."""


def read(window):
    if window.trace is None:
        return None
    return 100.0 * window.trace["idle_share"]
