"""The busiest chip's busy seconds over the mean of all the chips' (layer:
device): 1.0 where the shards keep their chips equally busy. From the
traced window's per-device busy seconds. None with fewer than two devices
or none busy."""


def read(ctx):
    trace = ctx["trace"]
    busy = trace["busy_s_per_device"] if trace else []
    if len(busy) < 2 or not sum(busy):
        return None
    return max(busy) / (sum(busy) / len(busy))
