"""setup_s: process start to the first timed request or tick (host clock):
loading, building the fleet from the seed, compiling or loading the
programs from the compile cache, and the checked ticks."""


def read(ctx):
    return ctx.setup_s
