"""Rebuilds the maintenance controller published inside the window (the
collection's rebuild counter, polled); each one's trigger and delta log is on
the run's `maintenance` line."""


def read(run):
    return len(run.rebuilds_in_window())
