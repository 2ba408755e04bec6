"""Process start to the first instant of the measured window: cluster
start, the chip reached, weights made on the device from the seed, the
cell's shapes warmed, the reference check (train), the ramp (serve)."""


def read(ctx):
    return ctx["setup_s"]
