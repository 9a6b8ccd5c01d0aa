"""Share of the roofline reached by decode: the least time the window's
required decode work could take at the chip's peaks (``work.py``, per
lane step from the exit indices the engine returned) over the window's
decode wall time (the warm chunks' seconds), in percent."""


def read(r):
    if r.peaks is None or not r.chunks or not r.lane_steps:
        return None
    least = sum(r.work.least_seconds(r.work.decode_step_flops(r.sizes, e, c),
                                   r.work.decode_step_bytes(r.sizes, e, c),
                                   r.peaks)
                for e, c in r.lane_steps)
    wall = sum(c[0] for c in r.chunks)
    return 100.0 * least / wall
