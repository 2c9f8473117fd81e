"""Horner ladder of degree ``degree``: acc = x*p_d, then acc = acc*x + p_i
for i = d-1 .. 0."""


def consts(degree: int):
    return [f"p{i}" for i in range(degree + 1)]


def run(x, c, degree: int):
    acc = x * c[f"p{degree}"]
    for i in range(degree - 1, -1, -1):
        acc = acc * x
        acc = acc + c[f"p{i}"]
    return acc
