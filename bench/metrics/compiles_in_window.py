"""compiles_in_window (count): executables the program's cache had to
build inside the measured window (the rise of
``emulator.cache_stats()["misses"]`` across it). It should read 0."""


def read(ctx):
    return ctx.get("compiles")
