"""A number the job reported, times `scale`."""


def read(window, key, scale=1.0):
    v = window.scalars.get(key)
    return None if v is None else scale * v
