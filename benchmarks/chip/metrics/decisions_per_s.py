"""Routing decisions completed per second: in an open loop those inside
the window over the window; in a closed loop every query sent in the window,
over the time until the last of them was decided."""


def read(run):
    return run.decided / run.rate_s
