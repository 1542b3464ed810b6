"""Work over time, both taken over the whole window: ``samples[work]`` /
``samples[seconds]``."""


def read(reading, work: str, seconds: str):
    samples = reading.outcome["samples"]
    if not samples.get(seconds):
        return None
    return samples[work] / samples[seconds]
