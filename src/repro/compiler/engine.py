"""Batched schedule-evaluation engine over the real CKKS stack.

This is the execution core shared by the compiler's verification tests
(`repro.compiler.interp.CkksTraceInterpreter` is now a thin single-
sample wrapper) and the serving runtime's `CiphertextBackend`
(repro/runtime/ciphertext_backend.py): encode + encrypt slot batches,
evaluate every trace op homomorphically with genuine relinearization /
Galois keys, decrypt + decode the outputs.

Batching model
--------------
A `CtBatch` stacks B same-shaped ciphertexts as one ``(B, 2, L, N)``
uint64 array. Every homomorphic op is applied through ONE
``jax.jit(jax.vmap(...))`` dispatch over the whole stack — the batch
axis rides through the same NTT/modmul/keyswitch code (core/ops.py)
that a single ciphertext uses, so a serving batch of 8 ciphertexts
costs one XLA program launch per op, not eight. Key-switch digits are
batched the same way: the per-digit ModUp/BConv/NTT pipeline sees
``(B, |digit|, N)`` limbs in one dispatch. Compiled appliers are
memoized per (kind, batch, level, scale, knobs) so steady-state serving
never retraces.

With ``use_kernel_modmul`` the plaintext-multiply data product is
routed through the Pallas modmul kernel (repro/kernels/ops.py) with the
batch folded into the limb-row axis — literally one kernel dispatch
covering the whole batch (compiled on TPU, interpret mode elsewhere).

Plaintext constants are encoded once per (const expression, level,
scale) and memoized through a pluggable cache hook — the serving
backend plugs the runtime `KeyCache` in here, so stage constants are
encoded on first use and *reused across batches* with real residency
accounting. Galois/relin key generation reports its evk footprint
through ``on_key_load`` for the same reason.

Scale handling follows core/linalg.py exactly (see the module
docstring of repro.compiler.interp for the invariants): same-level
operands of an add have structurally identical scales; across a level
gap the deeper operand is brought down *exactly* with a compensating
unit pmul (`linalg.adjust_to` semantics, batched here).

`bootstrap` ops execute as an exact refresh (decrypt -> re-encode at
the target level -> re-encrypt): the semantic contract of
bootstrapping without the minutes-long EvalMod chain; the full
approximate pipeline lives in core/bootstrap.py and is what the cost
model bills for.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import ntt as nttm
from repro.core import ops as hops
from repro.core.ciphertext import Ciphertext, KeySwitchKey, Plaintext
from repro.core.context import CkksContext
from repro.core.encoder import CkksEncoder
from repro.core.encryptor import CkksEncryptor
from repro.core.params import CkksParams
from repro.core.trace import FheOp, FheTrace, evk_bytes
from repro.obs.hook import layer


# ---------------------------------------------------------------------------
# const expressions (derived plaintexts minted by the passes; see ir.py)
# ---------------------------------------------------------------------------

def resolve_cexpr(expr, consts: Dict[str, np.ndarray]) -> np.ndarray:
    """Evaluate a derived-const expression (see ir.py) to a slot vector."""
    tag = expr[0]
    if tag == "ref":
        return np.asarray(consts[expr[1]])
    if tag == "mul":
        return resolve_cexpr(expr[1], consts) * resolve_cexpr(expr[2], consts)
    if tag == "add":
        return resolve_cexpr(expr[1], consts) + resolve_cexpr(expr[2], consts)
    if tag == "rot":
        # rotate(step): out[i] = in[i + step]
        return np.roll(resolve_cexpr(expr[1], consts), -expr[2], axis=-1)
    raise ValueError(f"unknown const expression {expr!r}")


def op_cexpr(op: FheOp):
    """An op's const expression; a bare named const if no cexpr meta.
    (Never index ``meta['const']`` as an eager .get default — ops minted
    by passes may carry only the cexpr.)"""
    expr = op.meta.get("cexpr")
    return expr if expr is not None else ("ref", op.meta["const"])


def const_vec(op: FheOp, consts: Dict[str, np.ndarray],
              slots: int) -> np.ndarray:
    v = resolve_cexpr(op_cexpr(op), consts)
    assert v.shape[-1] == slots, f"const for op {op.idx} has {v.shape} slots"
    return v


def galois_element(op: FheOp, params: CkksParams) -> Optional[int]:
    """The Galois element whose key ``op`` switches to, or None when it
    needs no Galois key (not a rotation, or a rotation by 0 slots)."""
    if op.kind == "rotate":
        step = op.meta["step"] % params.slots
        return nttm.galois_element(step, params.n) if step else None
    if op.kind == "conjugate":
        return 2 * params.n - 1
    return None


def _const_key(op: FheOp) -> str:
    """Stable human-readable identity of an op's const expression."""
    from repro.compiler.ir import cexpr_name
    return cexpr_name(op_cexpr(op))


# ---------------------------------------------------------------------------
# batched ciphertexts
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CtBatch:
    """B stacked ciphertexts sharing one (level, scale)."""
    data: jnp.ndarray            # (B, 2, level+1, N) uint64, NTT domain
    level: int
    scale: float

    @property
    def batch(self) -> int:
        return self.data.shape[0]

    @property
    def n_limbs(self) -> int:
        return self.level + 1


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: the name a jitted applier compiles and
    profiles under (the op kind, not ``<lambda>``)."""
    def applier(*a):
        return fn(*a)
    applier.__name__ = applier.__qualname__ = name
    return applier


def _observe_stage(span: layer, stage) -> None:
    """A served stage's wall is its compute: its tracer span carries it
    as ``compute_s`` (critical_path splits service by it), and armed
    telemetry takes it at the stage's real end."""
    sec = span.seconds
    span.annotate(compute_s=sec)
    tel = span.telemetry
    if tel is not None:
        t = span.end_at
        tel.counter("fhe_partition_busy_seconds",
                    partition=stage.partition).inc(t, sec)
        tel.histogram("fhe_stage_wall_seconds",
                      stage=stage.idx).observe(t, sec)


def _default_cache_factory() -> Callable:
    memo: Dict = {}

    def cache(key, nbytes, loader):
        if key not in memo:
            memo[key] = loader()
        return memo[key]
    return cache


class CkksEngine:
    """Executes traces/schedules on encrypted slot batches.

    Keys (secret, relin, per-element Galois) are generated once and
    cached across runs, so verifying a workload under several pass
    configurations — or serving many batches — pays keygen once.
    """

    def __init__(self, params: CkksParams, seed: int = 7,
                 const_cache: Optional[Callable] = None,
                 on_key_load: Optional[Callable[[Tuple, int], None]] = None,
                 use_kernel_modmul: bool = False,
                 use_kernels: bool = False):
        self.params = params
        self.ctx = CkksContext(params)
        # kernel route: every NTT runs as the Pallas limb NTT over these
        # tables (on a TPU the u64 library NTT costs over a minute of
        # compilation per shape, the kernel a couple of seconds)
        self.ntt_tables = None
        if use_kernels:
            from repro.kernels.limb_ntt import LimbNtt, context_tables
            self.ntt_tables = context_tables(self.ctx)
            self.ctx = self.ctx.with_transform(LimbNtt(self.ntt_tables))
        self.encoder = CkksEncoder(self.ctx)
        self.encryptor = CkksEncryptor(self.ctx, seed=seed)
        self.sk = self.encryptor.keygen()
        self.rk = self.encryptor.relin_keygen(self.sk)
        self._gks: Dict[int, KeySwitchKey] = {}
        # (kind, batch, levels, scales, knobs) -> jit(vmap(op)) applier
        self._opfns: Dict[Tuple, Callable] = {}
        self.const_cache = const_cache or _default_cache_factory()
        self.on_key_load = on_key_load
        # `use_kernels` routes every keyswitch (_hmul/_galois) through the
        # fused Pallas pipeline (kernels/keyswitch.py) AND the pmul data
        # product through the modmul kernel; `use_kernel_modmul` is the
        # narrower pre-existing switch (pmul only). Both are bit-exact
        # vs the library path, so flipping them never changes decrypts.
        self.use_kernels = use_kernels
        self.use_kernel_modmul = use_kernel_modmul or use_kernels
        self._fks = None
        if on_key_load is not None:
            on_key_load(("relin",), evk_bytes(params))

    # -- tolerance -----------------------------------------------------------

    @property
    def tolerance(self) -> float:
        """Conservative decrypt-error bound for this parameter set: the
        scheme's rounding/noise floor grows ~linearly in N and shrinks
        with the scale; the constant absorbs depth (empirically a few
        bits above observed error on the registered workloads)."""
        return 512.0 * self.params.n / 2.0 ** self.params.log_scale

    # -- keys ----------------------------------------------------------------

    def _gk(self, elt: int) -> KeySwitchKey:
        if elt not in self._gks:
            self._gks.update(self.encryptor.galois_keygen(self.sk, [elt]))
            if self.on_key_load is not None:
                self.on_key_load(("gk", elt), evk_bytes(self.params))
        return self._gks[elt]

    # -- encrypt / decode ----------------------------------------------------

    def encrypt(self, v: np.ndarray, level: int) -> Ciphertext:
        scale = 2.0 ** self.params.log_scale
        pt = Plaintext(self.encoder.encode(v, scale, level), level, scale)
        return self.encryptor.encrypt_sk(pt, self.sk)

    def encrypt_batch(self, vs: np.ndarray, level: int) -> CtBatch:
        """vs: (B, slots) complex -> one (B, 2, L, N) stack."""
        vs = np.atleast_2d(np.asarray(vs))
        cts = [self.encrypt(vs[i], level) for i in range(vs.shape[0])]
        return CtBatch(jnp.stack([c.data for c in cts]), level,
                       cts[0].scale)

    def decode(self, ct: Ciphertext) -> np.ndarray:
        return self.decode_batch(CtBatch(ct.data[None], ct.level,
                                         ct.scale))[0]

    def decode_batch(self, cb: CtBatch) -> np.ndarray:
        """(B, slots) decodes: one program decrypts the batch and takes
        every limb to coefficients, one lifts them to mixed-radix digits
        (both on the device); the host finishes the lift and embeds."""
        with layer("decode", cts=cb.batch, limbs=cb.n_limbs):
            with layer("decrypt"):
                coeff = jax.block_until_ready(self._decrypt_coeffs(cb))
            return self.encoder.decode_coeffs(coeff, cb.scale, cb.level)

    def _decrypt_coeffs(self, cb: CtBatch) -> jnp.ndarray:
        """c0 + c1·s, then the inverse NTT: (B, L, N) coefficients."""
        from repro.core.encryptor import decrypt_data
        lvl = cb.level

        def build(ctx):
            idx = ctx.q_idx(lvl)

            def f(d, s):
                return ctx.intt(decrypt_data(d, s, ctx.q_all), idx)
            return jax.vmap(f, in_axes=(0, None))
        return self._opfn(("decrypt", cb.batch, lvl), build)(
            cb.data, self.sk.s_ntt)

    def encode_const(self, vec: np.ndarray, scale: float, level: int,
                     key: Optional[Tuple] = None) -> Plaintext:
        """Encode (and memoize through the cache hook) one plaintext.

        The key always includes a digest of the VALUE: a caller may
        rebind the same const name to new values between runs (the old
        interpreter re-encoded every run), and a name-only key would
        silently serve the stale encoding. Identical values still hit.
        """
        nbytes = (level + 1) * self.params.n * 8
        digest = hash(np.ascontiguousarray(vec).tobytes())
        k = ("pt",) + (key or ()) + (digest, level, float(scale))
        data = self.const_cache(
            k, nbytes, lambda: self.encoder.encode(vec, scale, level))
        return Plaintext(data, level, scale)

    # -- compiled batched op appliers ---------------------------------------

    def _opfn(self, key: Tuple, build: Callable) -> Callable:
        """Memoized jitted applier for one (kind, batch, level, ...)
        signature; ``build(ctx)`` returns the vmapped function over
        ``ctx``. On the kernel route the limb-NTT tables are an argument
        of the program, not constants baked into it."""
        fn = self._opfns.get(key)
        if fn is not None:
            return fn
        if self.ntt_tables is None:
            fn = jax.jit(_named(build(self.ctx), key[0]))
        else:
            from repro.kernels.limb_ntt import LimbNtt
            ctx = self.ctx
            jitted = jax.jit(_named(lambda tabs, *a: build(
                ctx.with_transform(LimbNtt(tabs)))(*a), key[0]))
            fn = functools.partial(jitted, self.ntt_tables)
        self._opfns[key] = fn
        return fn

    def _mod_switch(self, cb: CtBatch, level: int) -> CtBatch:
        assert level <= cb.level
        if level == cb.level:
            return cb
        return CtBatch(cb.data[:, :, : level + 1], level, cb.scale)

    def _adjust_to(self, cb: CtBatch, level: int, scale: float) -> CtBatch:
        """Batched linalg.adjust_to: exact (level, scale) landing via a
        unit pmul at a compensating plaintext scale."""
        assert cb.level > level
        cb = self._mod_switch(cb, level + 1)
        q_drop = self.ctx.primes[level + 1]
        pt_scale = scale * q_drop / cb.scale
        pt = self.encode_const(np.ones(self.params.slots), pt_scale,
                               level + 1, key=("unit",))
        key = ("adjust", cb.batch, cb.level, float(cb.scale), float(scale))

        def build(ctx):
            lvl, s = cb.level, cb.scale

            def f(d, ptd):
                out = hops.pmul(ctx, Ciphertext(d, lvl, s),
                                Plaintext(ptd, lvl, pt_scale))
                return out.data
            return jax.vmap(f, in_axes=(0, None))
        data = self._opfn(key, build)(cb.data, pt.data)
        return CtBatch(data, level, scale)       # exact by construction

    def _aligned(self, c0: CtBatch, c1: CtBatch) -> Tuple[CtBatch, CtBatch]:
        """Bring an hadd/hsub pair to one (level, scale); exact across a
        level gap, scale-tag coercion at equal level (see interp.py)."""
        lvl = min(c0.level, c1.level)

        def down(hi: CtBatch, partner_scale: float) -> CtBatch:
            if (hi.level > lvl
                    and abs(hi.scale / partner_scale - 1.0) > 1e-6):
                return self._adjust_to(hi, lvl, partner_scale)
            return self._mod_switch(hi, lvl)

        if c0.level > c1.level:
            c0 = down(c0, c1.scale)
        elif c1.level > c0.level:
            c1 = down(c1, c0.scale)
        rel = abs(c1.scale / c0.scale - 1.0)
        if rel > 1e-6:
            raise ValueError(
                f"scale-incompatible add at level {lvl}: "
                f"{c0.scale:.6e} vs {c1.scale:.6e} — the trace mixes "
                f"rescale disciplines on one add")
        if rel > 0:
            c1 = CtBatch(c1.data, c1.level, c0.scale)
        return c0, c1

    def _addsub(self, kind: str, c0: CtBatch, c1: CtBatch) -> CtBatch:
        c0, c1 = self._aligned(c0, c1)
        key = (kind, c0.batch, c0.level)

        def build(ctx):
            lvl, s = c0.level, c0.scale
            fn = hops.hadd if kind == "hadd" else hops.hsub

            def f(d0, d1):
                return fn(ctx, Ciphertext(d0, lvl, s),
                          Ciphertext(d1, lvl, s)).data
            return jax.vmap(f)
        return CtBatch(self._opfn(key, build)(c0.data, c1.data),
                       c0.level, c0.scale)

    # -- fused Pallas keyswitch route (kernels/keyswitch.py) -----------------

    @property
    def fused_ks(self):
        """Lazily-built FusedKeySwitch shared by every evk (relin and all
        Galois keys ride the same per-(batch, level) compiled pipeline)."""
        if self._fks is None:
            from repro.kernels.keyswitch import FusedKeySwitch
            self._fks = FusedKeySwitch(self.ctx, self.ntt_tables)
        return self._fks

    def _hmul_fused(self, c0: CtBatch, c1: CtBatch, lazy: bool) -> CtBatch:
        """HMul with the relinearization keyswitch on the fused kernels:
        jitted tensor product -> 4-kernel keyswitch of the whole d2 batch
        -> jitted combine (+ rescale). Bit-identical to `_hmul`."""
        lvl = min(c0.level, c1.level)
        c0 = self._mod_switch(c0, lvl)
        c1 = self._mod_switch(c1, lvl)
        key = ("hmul_tensor", c0.batch, lvl)

        def build_tensor(ctx):
            def f(d0, d1):
                from repro.core import modarith as ma
                q = ctx.q_all[: lvl + 1][:, None]
                b0, a0 = d0[0], d0[1]
                b1, a1 = d1[0], d1[1]
                t0 = ma.mulmod(b0, b1, q)
                t1 = ma.addmod(ma.mulmod(a0, b1, q),
                               ma.mulmod(a1, b0, q), q)
                d2 = ma.mulmod(a0, a1, q)
                return jnp.stack([t0, t1]), d2
            return jax.vmap(f)
        d01, d2 = self._opfn(key, build_tensor)(c0.data, c1.data)
        km = self.fused_ks.ksk_mont("relin", self.rk.data)
        e0, e1 = self.fused_ks.apply(d2, lvl, km)
        ckey = ("hmul_combine", c0.batch, lvl)

        def build_combine(ctx):
            def f(d, e0_, e1_):
                from repro.core import modarith as ma
                q = ctx.q_all[: lvl + 1][:, None]
                return jnp.stack([ma.addmod(d[0], e0_, q),
                                  ma.addmod(d[1], e1_, q)])
            return jax.vmap(f)
        data = self._opfn(ckey, build_combine)(d01, e0, e1)
        out = CtBatch(data, lvl, c0.scale * c1.scale)
        return out if lazy else self._rescale(out)

    def _hmul(self, c0: CtBatch, c1: CtBatch, lazy: bool) -> CtBatch:
        if self.use_kernels:
            return self._hmul_fused(c0, c1, lazy)
        lvl = min(c0.level, c1.level)
        key = ("hmul", c0.batch, c0.level, c1.level, lazy)

        def build(ctx):
            l0, l1 = c0.level, c1.level
            s0, s1 = c0.scale, c1.scale

            def f(d0, d1, rkd):
                out = hops.hmul(ctx, Ciphertext(d0, l0, s0),
                                Ciphertext(d1, l1, s1),
                                KeySwitchKey(rkd), do_rescale=not lazy)
                return out.data
            return jax.vmap(f, in_axes=(0, 0, None))
        data = self._opfn(key, build)(c0.data, c1.data, self.rk.data)
        if lazy:
            return CtBatch(data, lvl, c0.scale * c1.scale)
        return CtBatch(data, lvl - 1,
                       c0.scale * c1.scale / self.ctx.q_primes[lvl])

    def _rescale(self, cb: CtBatch) -> CtBatch:
        key = ("rescale", cb.batch, cb.level)

        def build(ctx):
            lvl, s = cb.level, cb.scale

            def f(d):
                return hops.rescale(ctx,
                                    Ciphertext(d, lvl, s)).data
            return jax.vmap(f)
        return CtBatch(self._opfn(key, build)(cb.data), cb.level - 1,
                       cb.scale / self.ctx.q_primes[cb.level])

    def _pmul_kernel(self, cb: CtBatch, pt: Plaintext) -> CtBatch:
        """Plaintext-multiply data product through the Pallas modmul
        kernel: the (B, 2, L) rows fold into the kernel's limb-row axis,
        so ONE dispatch covers the whole batch."""
        from repro.kernels import ops as kops
        b, _, lp, n = cb.data.shape
        key = ("pmul_kernel", b, cb.level)

        def build(ctx):
            primes = [ctx.primes[i] for i in range(lp)] * (2 * b)

            def f(d, ptd):
                a = d.reshape(2 * b * lp, n)
                w = jnp.tile(ptd[: lp], (2 * b, 1))
                return kops.modmul(a, w, primes).reshape(b, 2, lp, n)
            return f
        data = self._opfn(key, build)(cb.data, pt.data)
        return CtBatch(data, cb.level, cb.scale * pt.scale)

    def _pmul(self, cb: CtBatch, pt: Plaintext, lazy: bool) -> CtBatch:
        if self.use_kernel_modmul:
            out = self._pmul_kernel(cb, pt)
            return out if lazy else self._rescale(out)
        key = ("pmul", cb.batch, cb.level, lazy)

        def build(ctx):
            lvl, s, ps = cb.level, cb.scale, pt.scale

            def f(d, ptd):
                out = hops.pmul(ctx, Ciphertext(d, lvl, s),
                                Plaintext(ptd, lvl, ps),
                                do_rescale=not lazy)
                return out.data
            return jax.vmap(f, in_axes=(0, None))
        data = self._opfn(key, build)(cb.data, pt.data)
        if lazy:
            return CtBatch(data, cb.level, cb.scale * pt.scale)
        return CtBatch(data, cb.level - 1,
                       cb.scale * pt.scale / self.ctx.q_primes[cb.level])

    def _padd(self, cb: CtBatch, pt: Plaintext) -> CtBatch:
        key = ("padd", cb.batch, cb.level)

        def build(ctx):
            lvl, s = cb.level, cb.scale

            def f(d, ptd):
                return hops.padd(ctx, Ciphertext(d, lvl, s),
                                 Plaintext(ptd, lvl, s)).data
            return jax.vmap(f, in_axes=(0, None))
        return CtBatch(self._opfn(key, build)(cb.data, pt.data),
                       cb.level, cb.scale)

    def _galois_fused(self, cb: CtBatch, elt: int) -> CtBatch:
        """Galois automorphism with the keyswitch on the fused kernels:
        jitted NTT-domain permutation -> 4-kernel keyswitch of the
        rotated `a` batch -> jitted combine. Bit-identical to `_galois`."""
        gk = self._gk(elt)
        lvl = cb.level
        perm = self.ctx.eval_perm(elt)
        key = ("galois_rot", cb.batch, lvl)

        def build_rot(ctx):
            # the permutation is an argument: one program for every elt
            def f(d, perm_):
                return d[0][:, perm_], d[1][:, perm_]
            return jax.vmap(f, in_axes=(0, None))
        rot_b, rot_a = self._opfn(key, build_rot)(cb.data, perm)
        km = self.fused_ks.ksk_mont(("gk", elt), gk.data)
        e0, e1 = self.fused_ks.apply(rot_a, lvl, km)
        ckey = ("galois_combine", cb.batch, lvl)

        def build_combine(ctx):
            def f(b_rot, e0_, e1_):
                from repro.core import modarith as ma
                q = ctx.q_all[: lvl + 1][:, None]
                return jnp.stack([ma.addmod(b_rot, e0_, q), e1_])
            return jax.vmap(f)
        data = self._opfn(ckey, build_combine)(rot_b, e0, e1)
        return CtBatch(data, lvl, cb.scale)

    def _galois(self, cb: CtBatch, elt: int) -> CtBatch:
        if self.use_kernels:
            return self._galois_fused(cb, elt)
        gk = self._gk(elt)
        key = ("galois", cb.batch, cb.level, elt)

        def build(ctx):
            lvl, s = cb.level, cb.scale

            def f(d, gkd):
                return hops._apply_galois(ctx, Ciphertext(d, lvl, s),
                                          elt, KeySwitchKey(gkd)).data
            return jax.vmap(f, in_axes=(0, None))
        return CtBatch(self._opfn(key, build)(cb.data, gk.data),
                       cb.level, cb.scale)

    # -- op-by-op evaluation -------------------------------------------------

    def run_ops(self, ops: Sequence[FheOp], env: Dict[int, CtBatch],
                consts: Dict[str, np.ndarray], *, start_level: int,
                const_scope: Tuple = ()) -> List[CtBatch]:
        """Evaluate `ops` (any program-ordered slice of a trace) against
        `env`, mutating it in place. Returns the values produced (for
        completion barriers). Plaintext constants are cached under
        ``const_scope + (cexpr, level, scale)``."""
        slots = self.params.slots
        scale = 2.0 ** self.params.log_scale
        produced: List[CtBatch] = []
        for op in ops:
            if op.kind in ("input", "const"):
                continue
            a = [env[x] for x in op.args]
            lazy = bool(op.meta.get("lazy"))
            if op.kind in ("hadd", "hsub"):
                out = self._addsub(op.kind, a[0], a[1])
            elif op.kind == "hmul":
                out = self._hmul(a[0], a[1], lazy)
            elif op.kind == "pmul":
                v = const_vec(op, consts, slots)
                pt = self.encode_const(v, scale, a[0].level,
                                       key=const_scope + (_const_key(op),))
                out = self._pmul(a[0], pt, lazy)
            elif op.kind == "padd":
                v = const_vec(op, consts, slots)
                pt = self.encode_const(v, a[0].scale, a[0].level,
                                       key=const_scope + (_const_key(op),))
                out = self._padd(a[0], pt)
            elif op.kind in ("rotate", "conjugate"):
                elt = galois_element(op, self.params)
                out = a[0] if elt is None else self._galois(a[0], elt)
            elif op.kind == "rescale":
                out = self._rescale(a[0])
            elif op.kind == "bootstrap":
                target = op.level if op.level is not None else start_level
                out = self.encrypt_batch(self.decode_batch(a[0]), target)
            else:
                raise ValueError(op.kind)
            env[op.idx] = out
            produced.append(out)
        return produced

    # -- whole-trace / whole-schedule execution ------------------------------

    @staticmethod
    def _resolve_start(trace: FheTrace, start_level: Optional[int],
                       n_levels: int) -> int:
        if start_level is not None:
            return start_level
        in_op = trace.ops[trace.inputs[0]] if trace.inputs else None
        return (in_op.level if in_op is not None
                and in_op.level is not None else n_levels)

    def run_batch(self, trace: FheTrace, inputs: Sequence[np.ndarray],
                  consts: Optional[Dict[str, np.ndarray]] = None,
                  start_level: Optional[int] = None,
                  const_scope: Tuple = ()) -> List[np.ndarray]:
        """Encrypt (B, slots) inputs, execute, return (B, slots) decodes."""
        consts = consts or {}
        start = self._resolve_start(trace, start_level,
                                    self.params.n_levels)
        env: Dict[int, CtBatch] = {}
        for i, idx in enumerate(trace.inputs):
            env[idx] = self.encrypt_batch(np.asarray(inputs[i]), start)
        self.run_ops(trace.ops, env, consts, start_level=start,
                     const_scope=const_scope)
        return [self.decode_batch(env[o]) for o in trace.outputs]

    def run(self, trace: FheTrace, inputs: Sequence[np.ndarray],
            consts: Optional[Dict[str, np.ndarray]] = None,
            start_level: Optional[int] = None) -> List[np.ndarray]:
        """Single-sample compatibility API (the old interpreter's
        contract): 1-D slot vectors in, 1-D decodes out."""
        outs = self.run_batch(trace, [np.asarray(v)[None, :]
                                      for v in inputs],
                              consts, start_level)
        return [o[0] for o in outs]

    def run_schedule(self, schedule, inputs: Sequence[np.ndarray],
                     consts: Optional[Dict[str, np.ndarray]] = None,
                     start_level: Optional[int] = None,
                     const_scope: Tuple = ()
                     ) -> Tuple[List[np.ndarray], List[float]]:
        """Execute a compiled `PipelineSchedule` stage by stage on (B,
        slots) encrypted inputs, timing each stage (completion barrier
        per stage). Returns (decoded outputs, per-stage wall seconds) —
        the measured side of the fig18 calibration table."""
        trace = schedule.trace
        assert trace is not None, \
            "schedule carries no trace (mapper predates engine support)"
        consts = consts or {}
        start = self._resolve_start(trace, start_level,
                                    self.params.n_levels)
        env: Dict[int, CtBatch] = {}
        with layer("encrypt", inputs=len(trace.inputs), level=start):
            for i, idx in enumerate(trace.inputs):
                env[idx] = self.encrypt_batch(np.asarray(inputs[i]), start)
            jax.block_until_ready([c.data for c in env.values()])
        stage_seconds: List[float] = []
        for stage in schedule.stages:
            kinds = collections.Counter(op.kind for op in stage.ops
                                        if op.kind not in ("input", "const"))
            with layer("stage", stage=stage.idx, partition=stage.partition,
                       **kinds) as span:
                produced = self.run_ops(stage.ops, env, consts,
                                        start_level=start,
                                        const_scope=const_scope)
                jax.block_until_ready([c.data for c in produced])
            stage_seconds.append(span.seconds)
            _observe_stage(span, stage)
        return ([self.decode_batch(env[o]) for o in trace.outputs],
                stage_seconds)
