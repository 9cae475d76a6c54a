"""Store tick (``serve/kv.py``): device milliseconds of one tick that only
appends its batch to the pending ring, the program ``kv_tick_ring``,
averaged over the chips."""

from bench.programs import program_ms


def read(run):
    return program_ms(run.trace, "kv_tick_ring")
