"""One HELR logistic-regression iteration: a rotation tree for the inner
product, then a cubic sigmoid approximation. Its second input ``w`` is the
server's weight vector."""


def consts(rot_steps):
    return ["c1", "c3"]


def run(x, w, c, rot_steps):
    s = x * w
    for k in rot_steps:
        s = s + s.rotate(k)
    a = s * c["c1"]
    b = s * s
    cube = b * s
    return w + (a + cube * c["c3"]) * x
