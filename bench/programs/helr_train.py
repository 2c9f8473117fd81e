"""HELR training: ``iters`` applications of the HELR step of ``helr.py``,
carrying the weights ``w`` from step to step. A bootstrap the program
places is the identity here, as it is in its semantics."""


def consts(iters, rot_steps):
    return ["c1", "c3"]


def run(x, w, c, iters, rot_steps):
    for _ in range(iters):
        s = x * w
        for k in rot_steps:
            s = s + s.rotate(k)
        a = s * c["c1"]
        b = s * s
        cube = b * s
        w = w + (a + cube * c["c3"]) * x
    return w
