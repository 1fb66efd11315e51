"""Seconds from the start of the process to the start of the window:
loading, drawing the traffic, building the fleet, compiling, warming up."""


def read(rec):
    return rec["setup_s"]
