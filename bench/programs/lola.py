"""LoLa-style shallow inference: two plaintext-weight layers around a
square activation."""


def consts():
    return ["w1", "w2"]


def run(x, c):
    h = x * c["w1"]
    h = h + h.rotate(1)
    h = h * h
    return h * c["w2"]
