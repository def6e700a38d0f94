"""Sketchguard's own work.  Every own state and every broadcast is
count-sketched (a sign flip and an add per parameter: 2 P each), the
sketches are compared (negligible: S << P), the accepted neighbours'
broadcasts are averaged in full (a multiply-add per accepted edge and
parameter: at most 2 degree P a node) and blended with the own state
(3 P).  Bytes: own and broadcast read once, the new state written once.
"""

from benchmark.roofline.shapes import least, shapes


def work(n: int, degree: float, p: int, itemsize: int):
    return n * p * (4.0 + 2.0 * degree + 3.0), 3.0 * n * p * itemsize


def least_seconds(cell, peaks: dict, param_dtype: str):
    return least(*work(*shapes(cell, param_dtype)), peaks)
