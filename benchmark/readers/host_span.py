"""Seconds of one of the harness's own host-clock spans."""


def read(context, span: str):
    return context["spans"].get(span)
