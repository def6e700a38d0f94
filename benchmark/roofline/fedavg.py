"""FedAvg's own work: a multiply-add per edge and parameter, the own
state added and the sum divided (2 degree P + 2 P a node); own and
broadcast read once, the new state written once."""

from benchmark.roofline.shapes import least, shapes


def work(n, degree, p, itemsize):
    return n * p * (2.0 * degree + 2.0), 3.0 * n * p * itemsize


def least_seconds(cell, peaks, param_dtype):
    return least(*work(*shapes(cell, param_dtype)), peaks)
