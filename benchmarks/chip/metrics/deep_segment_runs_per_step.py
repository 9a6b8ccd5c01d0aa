"""Runs of segments 1..n-1 per decode lane step: a deep segment runs in a
lane step where some live row of the lane reached it, and runs once over
the lane whichever of its cohorts needed it, so each count is one read of
that segment's weights.  From the exit indices the engine returned for
the window's lane steps: a step runs as many deep segments as its deepest
row's exit."""


def read(r):
    if not r.lane_steps:
        return None
    return sum(max(e) for e, _ in r.lane_steps) / len(r.lane_steps)
