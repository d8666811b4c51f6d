"""Requests handed back inside the measured window, over its length."""


def read(run):
    n = sum(r["answered"] and 0.0 <= r["handback"] < run.seconds
            for r in run.records)
    return n / run.seconds
