"""Seconds the interpreter spent in garbage collection inside the
window, from ``gc.callbacks``."""


def read(rec):
    return rec["gc_s"]
