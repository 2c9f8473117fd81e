"""Compulsory HBM bytes of one batch through a compiled schedule.

Every op reads its ciphertext inputs and its plaintext constant once and
writes its output once (2 polynomials of l+1 limbs of N words per
ciphertext, a plaintext is one polynomial). An op with a keyswitch (hmul,
a rotation by a nonzero step, conjugate) also reads the evaluation key of
its active digits once per batch (``keyswitch.evk_bytes``). Each input is
encrypted (its ciphertext written) and each output decrypted (read).
Levels follow the ops from the start level: hmul and pmul drop one unless
marked lazy, rescale drops one, a bootstrap lands on its target level.
"""
from bench.roofline import WORD, load

_ks = load("keyswitch")


def _ct(limbs: int, n: int) -> int:
    return 2 * limbs * n * WORD


def batch_bytes(ops, inputs, outputs, start_level: int, batch: int,
                n: int, slots: int, alpha: int) -> int:
    """``ops``: the trace's ops in program order (kind, idx, args, meta,
    level attributes as ``repro.core.trace.FheOp`` has them)."""
    level = {}
    total = 0
    for op in ops:
        k = op.kind
        if k == "input":
            level[op.idx] = start_level
            total += batch * _ct(start_level + 1, n)
            continue
        if k == "const":
            continue
        ls = [level[a] for a in op.args]
        l = min(ls)
        lazy = bool(op.meta.get("lazy"))
        out = l
        ks = False
        if k == "hmul":
            out = l if lazy else l - 1
            ks = True
            total += batch * (_ct(ls[0] + 1, n) + _ct(ls[1] + 1, n))
        elif k in ("rotate", "conjugate"):
            ks = k == "conjugate" or op.meta.get("step", 0) % slots != 0
            total += batch * _ct(l + 1, n)
        elif k in ("pmul", "padd"):
            if k == "pmul":
                out = l if lazy else l - 1
            total += batch * _ct(l + 1, n) + (l + 1) * n * WORD
        elif k in ("hadd", "hsub"):
            total += batch * (_ct(ls[0] + 1, n) + _ct(ls[1] + 1, n))
        elif k == "rescale":
            out = l - 1
            total += batch * _ct(l + 1, n)
        elif k == "bootstrap":
            out = op.level if op.level is not None else start_level
            total += batch * _ct(l + 1, n)
        else:
            raise ValueError(f"no byte count for op kind {k!r}")
        if ks:
            total += _ks.evk_bytes(l, n, alpha)
        total += batch * _ct(out + 1, n)
        level[op.idx] = out
    for o in outputs:
        total += batch * _ct(level[o] + 1, n)
    return total
