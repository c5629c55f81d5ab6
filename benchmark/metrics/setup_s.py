"""setup_s: process start to the opening of the measured window (host
clock): imports, device init, board generation, the simulation's build
and warm-up (compile or compile-cache load) and one untimed run."""


def read(ctx):
    return ctx.setup_s
