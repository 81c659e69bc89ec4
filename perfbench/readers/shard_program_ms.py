"""Device milliseconds per execution of the programs whose name holds
``program``, on the slowest of the cell's chips: a program that runs on
every chip at once ends when its slowest shard does."""

from readers._chips import slowest_program


def read(ctx, program):
    hit = slowest_program(ctx, program)
    return 1e3 * hit[0] / hit[1] if hit else None
