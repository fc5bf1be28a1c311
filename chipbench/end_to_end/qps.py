"""Queries completed in the traffic loop's measured span, over its length
(host clock)."""


def read(run):
    done = run.in_window & run.log["ok"]
    return float(done.sum()) / run.window_s
