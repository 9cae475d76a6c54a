"""Merge cascade (``core/ccache.py``): device milliseconds of one
commit-launch tick, the program ``kv_tick_launch`` (the ring's append, the
dense delta's scatter, the launch of its exchange), averaged over the
chips."""

from bench.programs import program_ms


def read(run):
    return program_ms(run.trace, "kv_tick_launch")
