"""Share of the measured window in which no operation ran on the device
(layer: device), from the union of device op intervals in the trace."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
