"""Hypothesis property tests on system-level invariants."""
import numpy as np
import pytest
import jax.numpy as jnp

from _hyp import given, settings, st  # noqa: E402  (skips per-test)

from repro.compiler import PassConfig, optimize_trace, reference_eval
from repro.compiler.passes import PASS_ORDER
from repro.core import rns
from repro.core.params import find_ntt_primes, test_params as make_test_params
from repro.core.trace import FheOp, FheTrace, infer_levels
from repro.sharding.rules import default_rules, serving_rules, spec_for_shape


# ---------------------------------------------------------------------------
# sharding rules invariants
# ---------------------------------------------------------------------------

def _mesh(shape=(4, 4)):
    import jax
    return jax.sharding.AbstractMesh(tuple(shape), ("data", "model"))


@settings(max_examples=60, deadline=None)
@given(dims=st.lists(st.sampled_from([1, 2, 3, 8, 10, 16, 56, 128, 256]),
                     min_size=1, max_size=4),
       names=st.lists(st.sampled_from(["batch", "heads", "kv_heads", "mlp",
                                       "embed", "vocab", None]),
                      min_size=1, max_size=4))
def test_spec_resolution_always_valid(dims, names):
    """For ANY shape/logical combination: no mesh axis used twice, and
    every sharded dim is divisible by its axis product."""
    n = min(len(dims), len(names))
    dims, names = dims[:n], names[:n]
    mesh = _mesh()
    sizes = dict(mesh.shape)
    spec = spec_for_shape(mesh, names, dims)
    used = []
    for dim, entry in zip(dims, spec):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        used += list(axes)
        total = int(np.prod([sizes[a] for a in axes]))
        assert dim % total == 0, (dims, names, spec)
    assert len(used) == len(set(used)), f"axis reused: {spec}"


def test_serving_rules_no_data_on_cache_seq_conflict():
    mesh = _mesh()
    r = serving_rules()
    spec = spec_for_shape(mesh, ("layers", "batch", "kv_heads", "seq",
                                 "head_dim"), (4, 8, 1, 4096, 128), r)
    assert spec[3] == "model", "serving rules must shard cache seq on model"
    d = default_rules()
    spec_d = spec_for_shape(mesh, ("layers", "batch", "kv_heads", "seq",
                                   "head_dim"), (4, 8, 1, 4096, 128), d)
    assert spec_d[3] is None


# ---------------------------------------------------------------------------
# RNS / CRT invariants
# ---------------------------------------------------------------------------

PRIMES = [m.value for m in find_ntt_primes(28, 8, 4)]


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_crt_lift_roundtrip_property(seed):
    """crt_lift(residues(x)) == x for |x| < Q/2."""
    rng = np.random.default_rng(seed)
    big_q = int(np.prod([int(p) for p in PRIMES], dtype=object))
    xs = rng.integers(-2**60, 2**60, size=16)
    limbs = np.stack([(xs % p).astype(np.uint64) for p in PRIMES])
    lifted = rns.crt_lift_centered(limbs, PRIMES)
    assert all(int(a) == int(b) for a, b in zip(lifted, xs))


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_bconv_identity_basis_property(seed):
    """BConv from a basis to itself is the identity (qhat*qhat^-1 = 1)."""
    rng = np.random.default_rng(seed)
    tabs = rns.make_bconv_tables(PRIMES, PRIMES)
    v = np.stack([rng.integers(0, p, size=32, dtype=np.uint64)
                  for p in PRIMES])
    out = np.asarray(rns.bconv(jnp.asarray(v), tabs))
    big_q = int(np.prod([int(p) for p in PRIMES], dtype=object))
    # fast conversion: out == v + k*Q (mod p_i) with 0 <= k < n_src
    x = rns.crt_lift_centered(v, PRIMES)
    for i, p in enumerate(PRIMES):
        diff = (out[i].astype(object) - (x % p)) % p
        allowed = {(k * big_q) % p for k in range(len(PRIMES) + 1)}
        assert set(int(d) for d in diff) <= allowed


# ---------------------------------------------------------------------------
# compiler invariants on randomly generated well-formed traces
# ---------------------------------------------------------------------------

CKKS_PARAMS = make_test_params(log_n=8, n_levels=6, dnum=2, log_scale=26)
START_LEVEL = 5
N_CONSTS = 3
CONST_AMP = 0.25
PASS_NAMES = tuple(p.name for p in PASS_ORDER)

# an instruction is (kind, a, b, step, cidx): a/b index the value pool
# modulo its current size; "mul_rescale"/"pmul_rescale" emit a lazy mul
# followed by its explicit rescale (the only scale-sound way a raw
# rescale op appears in a trace — identical prime path to the eager op)
TRACE_KINDS = ("hadd", "hsub", "hmul", "pmul", "padd", "rotate",
               "conjugate", "mul_rescale", "pmul_rescale")


def build_trace(n_inputs, instrs, start_level=START_LEVEL):
    """Deterministically interpret an instruction spec into a
    well-formed FheTrace: level budget respected (ops that would drop
    below level 1 are skipped), slot magnitudes bounded so CKKS decrypt
    stays inside the first-modulus headroom."""
    ops = []

    def add(kind, args=(), **meta):
        op = FheOp(len(ops), kind, tuple(args), meta)
        ops.append(op)
        return op.idx

    inputs = [add("input", slot=i) for i in range(n_inputs)]
    # pool entries: (op idx, level, magnitude bound)
    pool = [(i, start_level, 1.0) for i in inputs]
    for kind, a, b, step, cidx in instrs:
        ia, la, ma = pool[a % len(pool)]
        ib, lb, mb = pool[b % len(pool)]
        cname = f"c{cidx % N_CONSTS}"
        cmag = CONST_AMP * 4.0
        if kind in ("hadd", "hsub"):
            nxt = (add(kind, (ia, ib)), min(la, lb), ma + mb)
        elif kind == "hmul":
            if min(la, lb) - 1 < 1:
                continue
            nxt = (add("hmul", (ia, ib)), min(la, lb) - 1, ma * mb)
        elif kind == "mul_rescale":
            if min(la, lb) - 1 < 1:
                continue
            h = add("hmul", (ia, ib), lazy=True)
            nxt = (add("rescale", (h,)), min(la, lb) - 1, ma * mb)
        elif kind == "pmul":
            if la - 1 < 1:
                continue
            nxt = (add("pmul", (ia,), const=cname), la - 1, ma * cmag)
        elif kind == "pmul_rescale":
            if la - 1 < 1:
                continue
            h = add("pmul", (ia,), const=cname, lazy=True)
            nxt = (add("rescale", (h,)), la - 1, ma * cmag)
        elif kind == "padd":
            nxt = (add("padd", (ia,), const=cname), la, ma + cmag)
        elif kind == "rotate":
            nxt = (add("rotate", (ia,), step=step), la, ma)
        elif kind == "conjugate":
            nxt = (add("conjugate", (ia,)), la, ma)
        else:
            raise ValueError(kind)
        if nxt[2] > 4.0:          # q0 headroom: keep |values| small
            continue
        pool.append(nxt)
    outputs = [pool[-1][0]]
    return FheTrace(ops=ops, inputs=inputs, outputs=outputs, consts=[])


def trace_io(trace, seed=0):
    slots = CKKS_PARAMS.slots
    rng = np.random.default_rng(seed)

    def vec():
        return 0.3 * (rng.normal(size=slots) + 1j * rng.normal(size=slots))
    ins = [vec() for _ in trace.inputs]
    cs = {f"c{i}": CONST_AMP * rng.normal(size=slots)
          for i in range(N_CONSTS)}
    return ins, cs


def check_pass_subset(trace, subset, seed=0):
    """The two tentpole invariants for one (trace, pass subset):
    semantics preserved on the plaintext oracle, and no applied
    non-bootstrap pass ever increased the OpCost-derived seconds."""
    infer_levels(trace, START_LEVEL)
    cfg = PassConfig(start_level=START_LEVEL,
                     bsgs_min_terms=4).with_passes(subset)
    opt, report = optimize_trace(trace, CKKS_PARAMS, cfg)
    ins, cs = trace_io(trace, seed)
    for va, vb in zip(reference_eval(trace, ins, cs),
                      reference_eval(opt, ins, cs)):
        np.testing.assert_allclose(va, vb, atol=1e-9)
    for s in report.passes:
        if s.name == "bootstrap" or not s.applied:
            continue
        if s.seconds_before is not None and s.seconds_after is not None:
            assert s.seconds_after <= s.seconds_before * (1 + 1e-9), \
                f"pass {s.name} violated never-more-expensive"
    return opt, report


@st.composite
def trace_specs(draw):
    n_inputs = draw(st.integers(1, 2))
    n_ops = draw(st.integers(3, 14))
    instrs = tuple(
        (draw(st.sampled_from(TRACE_KINDS)),
         draw(st.integers(0, 10 ** 6)), draw(st.integers(0, 10 ** 6)),
         draw(st.integers(-8, 8)), draw(st.integers(0, N_CONSTS - 1)))
        for _ in range(n_ops))
    return n_inputs, instrs


@st.composite
def pass_subsets(draw):
    return tuple(n for n in PASS_NAMES
                 if draw(st.booleans()))


@settings(max_examples=40, deadline=None)
@given(spec=trace_specs(), subset=pass_subsets(),
       seed=st.integers(0, 2 ** 31 - 1))
def test_optimize_trace_preserves_semantics_any_pass_subset(spec, subset,
                                                            seed):
    """For ANY well-formed random trace and ANY PassConfig subset:
    `optimize_trace` is semantics-preserving on the plaintext oracle and
    never violates the never-more-expensive OpCost guard."""
    trace = build_trace(*spec)
    check_pass_subset(trace, subset, seed)


@pytest.fixture(scope="module")
def property_engine():
    from repro.compiler.engine import CkksEngine
    return CkksEngine(CKKS_PARAMS, seed=7)


@settings(max_examples=6, deadline=None)
@given(spec=trace_specs(), subset=pass_subsets())
def test_optimize_trace_decrypt_equality_random(spec, subset,
                                                property_engine):
    """Random trace + random pass subset: the optimized trace decodes
    to the original's values through the REAL CKKS stack (the shared
    engine), within the parameter set's tolerance."""
    trace = build_trace(*spec)
    infer_levels(trace, START_LEVEL)
    cfg = PassConfig(start_level=START_LEVEL,
                     bsgs_min_terms=4).with_passes(subset)
    opt, _ = optimize_trace(trace, CKKS_PARAMS, cfg)
    ins, cs = trace_io(trace, 1234)
    a = property_engine.run(trace, ins, cs, start_level=START_LEVEL)
    b = property_engine.run(opt, ins, cs, start_level=START_LEVEL)
    tol = property_engine.tolerance
    for va, vb in zip(a, b):
        np.testing.assert_allclose(va, vb, atol=2 * tol)


# deterministic corner specs so the builder + invariants run even where
# hypothesis is unavailable (the strategies above then skip)
_FIXED_SPECS = [
    (1, (("pmul", 0, 0, 0, 0), ("rotate", 1, 0, 3, 0),
         ("hadd", 1, 2, 0, 0), ("mul_rescale", 3, 1, 0, 1),
         ("padd", 4, 0, 0, 2))),
    (2, (("hmul", 0, 1, 0, 0), ("pmul_rescale", 2, 0, 0, 1),
         ("hsub", 3, 0, 0, 0), ("rotate", 4, 0, -5, 0),
         ("conjugate", 5, 0, 0, 0), ("hadd", 6, 2, 0, 0))),
    (2, (("rotate", 0, 0, 1, 0), ("rotate", 2, 0, 1, 0),
         ("pmul", 3, 0, 0, 0), ("pmul", 2, 0, 0, 1),
         ("hadd", 4, 5, 0, 0), ("hadd", 6, 1, 0, 2),
         ("mul_rescale", 7, 7, 0, 0))),
]


@pytest.mark.parametrize("spec_i", range(len(_FIXED_SPECS)))
@pytest.mark.parametrize("subset", [(), ("dce", "cse"),
                                    ("fold", "rotation", "lazy_rescale"),
                                    PASS_NAMES])
def test_optimize_trace_fixed_specs(spec_i, subset):
    trace = build_trace(*_FIXED_SPECS[spec_i])
    assert len(trace.compute_ops()) >= 3
    check_pass_subset(trace, subset, seed=spec_i)


# ---------------------------------------------------------------------------
# data pipeline invariant
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(step=st.integers(0, 10000), batch=st.sampled_from([1, 2, 4]),
       seq=st.sampled_from([8, 16, 32]))
def test_dataset_labels_are_shifted_tokens(step, batch, seq):
    from repro.configs import get_config
    from repro.data.pipeline import SyntheticLMDataset
    cfg = get_config("granite-3-8b", smoke=True)
    ds = SyntheticLMDataset(cfg, batch=batch, seq=seq)
    b = ds.batch_at(step)
    assert b["tokens"].shape == (batch, seq)
    assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).all()
    assert (b["tokens"] >= 0).all() and (b["tokens"] < cfg.vocab).all()
