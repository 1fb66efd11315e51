"""Ends x periods folded and harvested inside the window, per second of
the window: all the work over all the time."""


def read(rec):
    return rec["folded"] / rec["window_s"]
