"""What is left of a whole after a part, from exact counts made by the
program over the window: ``100 * (1 - counters[part] / counters[of])``.
``None`` where the program makes no such count (a program from before the
counter) or counted nothing."""


def read(reading, part: str, of: str):
    counters = reading.outcome["counters"]
    if not counters.get(of):
        return None
    return 100.0 * (1.0 - counters[part] / counters[of])
