"""Front end: real updates over the update slots its ticks dispatched in
the window, in percent. A count; it repeats exactly."""


def read(run):
    slots = run.counters["slots"]
    if not slots:
        return None
    return 100.0 * run.counters["real_updates"] / slots
