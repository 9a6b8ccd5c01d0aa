"""Milliseconds per decode lane step: the window's warm decode chunks'
seconds (``DecodeChunk.seconds``, dispatch to host sync, via the flight
recorder's ``lane_chunk`` events) over the steps they ran."""


def read(r):
    steps = sum(c[1] for c in r.chunks)
    if not steps:
        return None
    return 1e3 * sum(c[0] for c in r.chunks) / steps
