"""Seconds XLA spent producing executables during set-up (a compile, or
a retrieval from the persistent cache), from JAX's
`backend_compile_duration` events."""


def read(window):
    return window.compile[0]
