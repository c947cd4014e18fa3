"""Set-up: process start to the first timed step or round (host clock).

Covers finding the chip, building and compiling (or loading from the
compilation cache) the system under test, making the weights, batches or
update pool on the device, and the warm-up steps or round."""


def read(r):
    return r.setup_s
