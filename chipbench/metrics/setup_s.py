"""Seconds from process start to the first measured second: imports, device
start, weights, the engine's bake, compilation (or the compile cache),
the scene pool and the warm-up traffic."""


def read(r):
    return r.setup_s
