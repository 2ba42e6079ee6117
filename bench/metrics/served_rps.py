"""served_rps: answers that reached the client inside the window, per second
of the window (host clock).  Ramp-up and the answers still in flight at the
close are part of the window, as a user sees them."""


def read(run):
    t0, t1 = run.window
    req = run.req
    answered = (req["status"] == 0) & (req["done"] >= t0) & (req["done"] <= t1)
    return float(answered.sum() / (t1 - t0))
