"""95th percentile, over every request sent in the window, of the client's
time from send to reply; requests in flight at the close are waited for. In
this saturated closed loop Little's law ties it to the tokens a second, and its
runs spread too widely (5.7 % at 30 s) to carry a bound, so it stands here."""


def read(run):
    return run["end_to_end"].get("request_p95_ms")
