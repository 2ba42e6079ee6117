"""conv_fan_out.new: layer 0's counted operations over twice the window's
input events, the destinations the harness counts per event."""


def read(run):
    if not run.work:
        return None
    return run.work[0][0] / (2.0 * float(run.req["events_in"].sum()))
