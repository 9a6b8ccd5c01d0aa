"""Programs compiled or loaded from the persistent cache between window
start and end, counted by the harness's ``jax.monitoring`` listener on the
backend-compile event."""


def read(r):
    return r.compiles
