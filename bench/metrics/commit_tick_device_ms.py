"""Merge cascade: device milliseconds of one commit tick that does not
overlap, the program ``kv_tick_commit`` (the ring's append, the rings'
route home and the scatter of the home rows), averaged over the chips."""

from bench.programs import program_ms


def read(run):
    return program_ms(run.trace, "kv_tick_commit")
