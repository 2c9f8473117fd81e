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

`bootstrap` ops run real CKKS bootstrapping whenever the parameter set
declares bootstrap settings (`CkksParams.has_bootstrap`): ModRaise,
the factored CoeffToSlot, EvalMod on the real and imaginary parts
batched together, and the factored SlotToCoeff (core/bootstrap.py's
`BootstrapPlan`), all on the batch through the appliers above and the
fused keyswitch, landing at exactly the level the compiler assumed
(``PassConfig.bootstrap_to``) with the scale of a fresh encryption.
Its Galois keys and DFT diagonals are made on first use and pinned.
A parameter set without bootstrap settings takes the test-scale refresh
instead (`_test_scale_refresh`: decrypt and re-encrypt under the
server's key), which only toy ring sizes reach.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
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
from repro.core.params import BootstrapLevelError, CkksParams
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


TEST_SCALE_MAX_LOG_N = 12    # largest ring the test-scale refresh serves


def _ready(cb: CtBatch) -> CtBatch:
    jax.block_until_ready(cb.data)
    return cb


def _backsolve(s_end: float, primes: Sequence[int]) -> float:
    """The scale s_0 whose squarings, each rescaled by the next of
    ``primes`` (s_{k+1} = s_k^2 / primes[k]), end at ``s_end``."""
    log = math.log2(s_end)
    for q in reversed(primes):
        log = (log + math.log2(q)) / 2
    return 2.0 ** log


def mod_raise_fn(ctx: CkksContext, level: int) -> Callable:
    """ModRaise of one ciphertext's data (2, L, N): q0's limb times the
    gain (a residue mod q0), to coefficients, centred, and into every
    prime of ``level`` (NTT domain)."""
    from repro.core import modarith as ma
    q0 = ctx.primes[0]
    idx = ctx.q_idx(level)
    q = ctx.q_all[: level + 1][:, None]
    u = jnp.uint64

    def f(d, gain):
        c = ctx.intt(ma.mulmod(d[:, :1], gain, u(q0)), [0])
        r = c % q                                           # (2, L, N)
        return ctx.ntt(jnp.where(c > u(q0 // 2),
                                 ma.submod(r, u(q0) % q, q), r), idx)
    return f


def _observe_bootstrap(span: layer) -> None:
    """Armed telemetry takes each bootstrap's keyswitches and wall at
    its real end."""
    tel = span.telemetry
    if tel is not None:
        t = span.end_at
        tel.counter("fhe_bootstrap_keyswitches").inc(
            t, span.attrs["keyswitches"])
        tel.histogram("fhe_bootstrap_wall_seconds").observe(t, span.seconds)


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
        self.n_keyswitches = 0           # relinearizations and rotations
        self._bts_pts: Dict[Tuple, Plaintext] = {}   # pinned bootstrap pts
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

    @property
    def bootstrap_tolerance(self) -> float:
        """Decrypt-error bound of one bootstrap. EvalMod computes at a
        scale near Δ on y = x / (K+1), x = t / q0, and its result is read
        back as the message: times (K+1) q0 / (gain Δ). So the bound is
        this parameter set's CKKS tolerance times that factor."""
        p = self.params
        return self.tolerance * (p.bts_k_range + 1) * p.bts_msg_ratio

    # -- keys ----------------------------------------------------------------

    def _gk(self, elt: int) -> Optional[KeySwitchKey]:
        """The Galois key for ``elt``, made on first use. On the kernel
        route only the fused keyswitch's Montgomery copy is kept (None
        here), so each key occupies HBM once."""
        if elt not in self._gks:
            gk = self.encryptor.galois_keygen(self.sk, [elt])[elt]
            if self.use_kernels:
                self.fused_ks.ksk_mont(("gk", elt), gk.data)
                gk = None
            self._gks[elt] = gk
            if self.on_key_load is not None:
                self.on_key_load(("gk", elt), evk_bytes(self.params))
        return self._gks[elt]

    def pinned(self) -> List[Tuple[Tuple, int]]:
        """(key, bytes) of everything the engine holds for good: the
        relinearization key, each Galois key, each bootstrap plaintext."""
        nb = evk_bytes(self.params)
        return ([(("relin",), nb)] + [(("gk", e), nb) for e in self._gks]
                + [(("bts",) + k, (pt.level + 1) * self.params.n * 8)
                   for k, pt in self._bts_pts.items()])

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
        unit multiply at a compensating plaintext scale, then a rescale."""
        assert cb.level > level
        cb = self._mod_switch(cb, level + 1)
        q_drop = self.ctx.primes[level + 1]
        out = self._rescale(self._mul_const(cb, 1.0, scale * q_drop
                                            / cb.scale))
        return CtBatch(out.data, level, scale)   # exact by construction

    def _residues(self, k: int, level: int) -> jnp.ndarray:
        """The integer ``k`` mod each prime of ``level``'s basis."""
        return jnp.asarray(np.array([k % q for q in
                                     self.ctx.q_primes[: level + 1]],
                                    dtype=np.uint64))

    def _mul_const(self, cb: CtBatch, value: float,
                   pt_scale: float) -> CtBatch:
        """Multiply by the real constant ``value`` encoded at
        ``pt_scale``, without a rescale. A constant slot vector encodes
        to the constant polynomial round(value * pt_scale), which is that
        integer at every NTT point: one multiply per limb, and the same
        product a pmul by its encoded plaintext gives."""
        k = int(round(value * pt_scale))
        key = ("mul_const", cb.batch, cb.level)

        def build(ctx):
            q = ctx.q_all[: cb.n_limbs][:, None]

            def f(d, r):
                from repro.core import modarith as ma
                return ma.mulmod(d, r[:, None], q)
            return jax.vmap(f, in_axes=(0, None))
        data = self._opfn(key, build)(cb.data, self._residues(k, cb.level))
        return CtBatch(data, cb.level, cb.scale * pt_scale)

    def _add_const(self, cb: CtBatch, value: float) -> CtBatch:
        """Add the real constant ``value`` at the batch's scale (a padd
        of the constant slot vector, as one add per limb)."""
        k = int(round(value * cb.scale))
        key = ("add_const", cb.batch, cb.level)

        def build(ctx):
            q = ctx.q_all[: cb.n_limbs][:, None]

            def f(d, r):
                from repro.core import modarith as ma
                return jnp.stack([ma.addmod(d[0], r[:, None], q), d[1]])
            return jax.vmap(f, in_axes=(0, None))
        data = self._opfn(key, build)(cb.data, self._residues(k, cb.level))
        return CtBatch(data, cb.level, cb.scale)

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
        self.n_keyswitches += 1
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
        self._gk(elt)
        lvl = cb.level
        perm = self.ctx.eval_perm(elt)
        key = ("galois_rot", cb.batch, lvl)

        def build_rot(ctx):
            # the permutation is an argument: one program for every elt
            def f(d, perm_):
                return d[0][:, perm_], d[1][:, perm_]
            return jax.vmap(f, in_axes=(0, None))
        rot_b, rot_a = self._opfn(key, build_rot)(cb.data, perm)
        km = self.fused_ks.ksk_mont(("gk", elt), None)
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
        self.n_keyswitches += 1
        if self.use_kernels:
            return self._galois_fused(cb, elt)
        gk = self._gk(elt)
        key = ("galois", cb.batch, cb.level)

        def build(ctx):
            lvl = cb.level
            q = ctx.q_all[: lvl + 1][:, None]

            # hops._apply_galois with the permutation an argument: one
            # program for every element
            def f(d, gkd, perm):
                from repro.core import modarith as ma
                e0, e1 = hops.key_switch(ctx, d[1][:, perm], lvl,
                                         KeySwitchKey(gkd))
                return jnp.stack([ma.addmod(d[0][:, perm], e0, q), e1])
            return jax.vmap(f, in_axes=(0, None, None))
        return CtBatch(self._opfn(key, build)(cb.data, gk.data,
                                              self.ctx.eval_perm(elt)),
                       cb.level, cb.scale)

    # -- bootstrapping -------------------------------------------------------

    def bootstrap(self, cb: CtBatch, target: int) -> CtBatch:
        """Refresh ``cb`` to exactly level ``target`` at the scale of a
        fresh encryption: real CKKS bootstrapping where the parameter set
        has bootstrap settings, else the test-scale refresh. Nothing
        leaves the device in between; each phase ends in a barrier so its
        span holds its own device time."""
        p = self.params
        if not p.has_bootstrap:
            return self._test_scale_refresh(cb, target)
        from repro.core.bootstrap import bootstrap_plan
        raise_level = p.bts_raise_level(target)
        plan = bootstrap_plan(p)
        # the integer gain that brings this input to the message ratio
        q0 = self.ctx.primes[0]
        gain = max(1, round(q0 / (cb.scale * p.bts_msg_ratio)))
        ratio = q0 / (gain * cb.scale)
        ks0 = self.n_keyswitches
        with layer("bootstrap", cts=cb.batch, level_in=cb.level,
                   level_out=target) as span:
            with layer("mod_raise"):
                x = _ready(self._mod_raise(cb, raise_level, gain))
            with layer("cts"):
                y = _ready(self._coeff_to_slot(x, plan))
            with layer("evalmod"):
                g = _ready(self._eval_mod(y, plan, ratio))
            with layer("stc"):
                out = _ready(self._slot_to_coeff(g, plan, ratio))
            span.annotate(keyswitches=self.n_keyswitches - ks0)
        _observe_bootstrap(span)
        assert out.level == target
        return out

    def _test_scale_refresh(self, cb: CtBatch, target: int) -> CtBatch:
        """The refresh of a parameter set without bootstrap settings:
        decrypt under the server's secret key and encrypt again at
        ``target``. Exact, and no bootstrap at all: it serves the CPU
        tests' toy rings, and a ring above logN=12 (every benchmark
        configuration) refuses it."""
        if self.params.log_n > TEST_SCALE_MAX_LOG_N:
            raise BootstrapLevelError(target, self.params)
        return self.encrypt_batch(self.decode_batch(cb), target)

    def _bts_pt(self, key: Tuple, vec, scale: float,
                level: int) -> Plaintext:
        """A bootstrap plaintext, encoded on first use and held (pinned)
        for the engine's life."""
        pt = self._bts_pts.get(key)
        if pt is None:
            pt = self._bts_pts[key] = Plaintext(
                self.encoder.encode(vec, scale, level), level, scale)
            if self.on_key_load is not None:
                self.on_key_load(("bts",) + key,
                                 (level + 1) * self.params.n * 8)
        return pt

    def _mod_raise(self, cb: CtBatch, level: int, gain: int) -> CtBatch:
        """Keep q0's limb, multiply it by ``gain`` (exact), and lift its
        centred residues into every prime of ``level``: the ciphertext
        then holds t = gain·m + q0·I with |I| <= K. The scale declared,
        2·q0·(K+1), makes the real and imaginary parts of CoeffToSlot's
        output y = t / (q0 (K+1)), in [-1, 1]."""
        q0 = self.ctx.primes[0]
        key = ("mod_raise", cb.batch, level)
        data = self._opfn(key, lambda ctx: jax.vmap(
            mod_raise_fn(ctx, level), in_axes=(0, None)))(
                cb.data, jnp.uint64(gain % q0))
        return CtBatch(data, level,
                       2.0 * q0 * (self.params.bts_k_range + 1))

    def _dft_level(self, x: CtBatch, lv, name: Tuple,
                   shrink: float) -> CtBatch:
        """One level of a factored DFT (`core.bootstrap.DftLevel`) by
        baby-step giant-step, its diagonals encoded at ``shrink`` times
        the prime the level drops (so the scale falls by that factor);
        one rescale at the end."""
        pt_scale = shrink * self.ctx.q_primes[x.level]
        rots = {0: x}
        out = None
        for i, terms in lv.terms.items():
            inner = None
            for j, diag in terms:
                if j not in rots:
                    rots[j] = self._galois(
                        x, self.ctx.rotation_element(lv.gap * j))
                pt = self._bts_pt(name + (i, j, x.level), diag, pt_scale,
                                  x.level)
                t = self._pmul(rots[j], pt, lazy=True)
                inner = t if inner is None else self._addsub("hadd", inner,
                                                             t)
            if i:
                inner = self._galois(
                    inner, self.ctx.rotation_element(lv.gap * lv.baby * i))
            out = inner if out is None else self._addsub("hadd", out, inner)
        return self._rescale(out)

    def _coeff_to_slot(self, x: CtBatch, plan) -> CtBatch:
        """CoeffToSlot, then the real and imaginary parts (w + conj w and
        -i (w - conj w)) stacked as one batch of 2B, at the scale EvalMod's
        ladder starts from."""
        lvl = x.level - len(plan.cts)
        s_y = _backsolve(2.0 ** self.params.log_scale, self.ctx.q_primes[
            lvl - plan.ladder_depth + 1: lvl + 1][::-1])
        shrink = (s_y / x.scale) ** (1.0 / len(plan.cts))
        for k, lv in enumerate(plan.cts):
            x = self._dft_level(x, lv, ("cts", k), shrink)
        xc = self._galois(x, 2 * self.params.n - 1)
        re = self._addsub("hadd", x, xc)
        im = self._pmul(self._addsub("hsub", x, xc),
                        self._bts_pt(("-i", lvl), -1j, 1.0, lvl), lazy=True)
        return CtBatch(jnp.concatenate([re.data, im.data]), lvl, x.scale)

    def _same_level(self, a: CtBatch, b: CtBatch) -> Tuple[CtBatch, CtBatch]:
        """Bring the higher operand exactly to the other's (level, scale),
        so a product's scale depends on its level alone."""
        if a.level > b.level:
            a = self._adjust_to(a, b.level, b.scale)
        elif b.level > a.level:
            b = self._adjust_to(b, a.level, a.scale)
        return a, b

    def _double_minus(self, t: CtBatch, sub) -> CtBatch:
        """2 t - sub (``sub``: a batch, or a constant)."""
        t = self._addsub("hadd", t, t)
        if isinstance(sub, CtBatch):
            return self._addsub("hsub", t, sub)
        return self._add_const(t, -sub)

    def _eval_mod(self, y: CtBatch, plan, ratio: float) -> CtBatch:
        """EvalMod on the stacked parts y = x / (K+1): the cosine's
        Chebyshev series on the power-of-two ladder (T_2d = 2 T_d^2 - 1,
        T_{lo+hi} = 2 T_lo T_hi - T_{lo-hi}; every value of one level at
        one scale), then r double angles, so the slots hold
        sin(2 pi x) ~ 2 pi x', x' = gain m / q0, the message over this
        input's message ratio. It ends at Δ·ratio / bts_msg_ratio, the
        scale that `_slot_to_coeff` reads back at a fixed one."""
        p = self.params
        deg, r = p.bts_cheb_degree, p.bts_double_angle
        q = self.ctx.q_primes
        low = y.level - plan.ladder_depth     # every term is brought here
        s_end = 2.0 ** p.log_scale * ratio / p.bts_msg_ratio
        s_sum = _backsolve(s_end, q[low - r: low][::-1])
        operands = {1}
        for i in range(2, deg + 1):
            lo = 1 << (i.bit_length() - 1)
            operands.update((lo // 2,) if lo == i else (lo, i - lo,
                                                        2 * lo - i))
        ts = {1: y}
        acc = None

        def fold(i, t):               # acc += c_i T_i at level `low`, lazy
            nonlocal acc
            t = self._mod_switch(t, low)
            term = self._mul_const(t, plan.cheb[i], s_sum * q[low] / t.scale)
            acc = term if acc is None else self._addsub("hadd", acc, term)

        for i in range(2, deg + 1):
            lo = 1 << (i.bit_length() - 1)
            if lo == i:
                h = ts[lo // 2]
                t = self._double_minus(self._hmul(h, h, lazy=False), 1.0)
            else:
                a, b = self._same_level(ts[lo], ts[i - lo])
                t = self._double_minus(self._hmul(a, b, lazy=False),
                                       ts[2 * lo - i])
            if i in operands:
                ts[i] = t
            else:
                fold(i, t)
        for i in sorted(ts):
            fold(i, ts.pop(i))
        c = self._add_const(self._rescale(acc), plan.cheb[0])
        for _ in range(r):
            c = self._double_minus(self._hmul(c, c, lazy=False), 1.0)
        return c

    def _slot_to_coeff(self, g: CtBatch, plan, ratio: float) -> CtBatch:
        """Undo the split (re + i·im), read the slots as the message's
        coefficients, and SlotToCoeff them down to the target level at
        the scale of a fresh encryption."""
        p = self.params
        half = g.batch // 2
        lvl = g.level
        re = CtBatch(g.data[:half], lvl, g.scale)
        im = self._pmul(CtBatch(g.data[half:], lvl, g.scale),
                        self._bts_pt(("+i", lvl), 1j, 1.0, lvl), lazy=True)
        z = self._addsub("hadd", re, im)
        delta = 2.0 ** p.log_scale
        # z holds sin(2 pi x) ~ 2 pi x' at scale Δ·ratio / bts_msg_ratio;
        # read at 2 pi Δ / bts_msg_ratio (fixed, so the diagonals are
        # too) it holds x'·ratio = m / s_in: the message's coefficients
        x = CtBatch(z.data, lvl, z.scale * 2 * math.pi / ratio)
        shrink = (p.bts_msg_ratio / (2 * math.pi)) ** (1.0 / len(plan.stc))
        for k, lv in enumerate(plan.stc):
            x = self._dft_level(x, lv, ("stc", k), shrink)
        return CtBatch(x.data, x.level, delta)

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
                out = self.bootstrap(a[0], target)
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
