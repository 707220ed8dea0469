"""Peak bytes in use on the fullest chip, as the backend reports it,
times `scale`; nothing where the backend reports none (XLA:CPU)."""


def read(window, scale=1.0):
    peak = window.device.get("memory_peak_bytes") if window.device else 0
    return scale * peak if peak else None
