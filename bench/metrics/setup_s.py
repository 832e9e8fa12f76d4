"""Seconds from the process's start to the window's first request:
start-up, weights, calibration, engine start and warm-up."""


def read(rec):
    return rec.setup_s
