"""One of the run's counters, scaled."""


def read(context, counter: str, scale: float = 1.0):
    value = context["counters"].get(counter)
    return None if value is None else value * scale
