"""Broker.lag (appended, not yet committed) at the window's close."""


def read(run):
    return run.lag_end
