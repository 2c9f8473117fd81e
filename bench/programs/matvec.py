"""Halevi-Shoup diagonal matrix-vector product over ``dim`` diagonals:
y = sum_i rotate(x, i) * d_i."""


def consts(dim: int):
    return [f"d{i}" for i in range(dim)]


def run(x, c, dim: int):
    acc = x * c["d0"]
    for i in range(1, dim):
        acc = acc + x.rotate(i) * c[f"d{i}"]
    return acc
