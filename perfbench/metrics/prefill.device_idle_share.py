"""Layer: device. Source: device_trace (1 minus the union of the
intervals in which an operation ran on the device, over the traced
slice of the window). Moves: the cell's throughput or tail."""


def read(run):
    return run.device_idle_share()
