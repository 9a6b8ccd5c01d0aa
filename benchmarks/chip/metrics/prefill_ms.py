"""Mean seconds of the window's lane prefills (the flight recorder's
``lane_prefill`` events: one per (re)prefill dispatch, closed after its
first tokens reached the host), in milliseconds."""


def read(r):
    if not r.prefills:
        return None
    return 1e3 * sum(p[0] for p in r.prefills) / len(r.prefills)
