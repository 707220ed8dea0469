"""Mean of what a telemetry histogram took in during the window:
added sum / added count, times `scale`."""


def read(window, name, scale=1.0):
    if window.before is None:
        return None
    count, total = window.hist_delta(name)
    return scale * total / count if count else None
