"""The share of the window, in percent, that a count of milliseconds made
by the program over the window takes: ``100 * counters[counter] / (the
window's seconds * 1000)``. ``None`` where the program makes no such count
(a program from before the counter); 0.0 where it counted nothing."""


def read(reading, counter: str):
    counters = reading.outcome["counters"]
    if counter not in counters:
        return None
    return 100.0 * counters[counter] / (reading.outcome["samples"]["window_seconds"] * 1e3)
