"""Merge cascade: device milliseconds of one landing tick, the program
``kv_tick_land`` (the launched aggregate's top exchange and its home rows
settled, then the ring's append), averaged over the chips."""

from bench.programs import program_ms


def read(run):
    return program_ms(run.trace, "kv_tick_land")
