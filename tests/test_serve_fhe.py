"""serve_fhe's entry point: failure exits, key-cache sizing, compile cache.

The served path must not finish with exit 0 when a phase failed, must
size its key cache for the evaluation keys it pins, and keeps JAX's
persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR`` says
or, without it, in a fixed directory of the checkout.
"""
import os

import jax
import pytest

from repro.launch import jax_cache
from repro.launch import serve_fhe as sf


def test_workload_with_no_batch_fails_the_run(monkeypatch, capsys):
    """One request for two registered workloads: the second serves no
    batch, and the ciphertext run exits non-zero saying so."""
    fn, n_in, consts = sf.WORKLOADS["lola"]
    monkeypatch.setattr(sf, "WORKLOADS", {"lola": (fn, n_in, consts),
                                          "lola_idle": (fn, n_in, consts)})
    with pytest.raises(SystemExit) as ei:
        sf.main(["--smoke", "--backend", "ciphertext", "--requests", "1",
                 "--tenants", "1"])
    assert ei.value.code == 1
    out = capsys.readouterr()
    assert "lola_idle" in out.err and "served no batch" in out.err
    assert "accuracy lola         batches=1" in out.out


def test_run_reports_success_without_exiting(monkeypatch):
    fn, n_in, consts = sf.WORKLOADS["lola"]
    monkeypatch.setattr(sf, "WORKLOADS", {"lola": (fn, n_in, consts)})
    res = sf.run(["--smoke", "--backend", "ciphertext", "--requests", "2",
                  "--tenants", "1"])
    assert res.failures == []
    assert res.metrics.decrypt_batches["lola"] >= 1
    assert res.warmup_s > 0 and res.serve_s > 0


def test_paper_params_need_more_than_the_old_cache_default():
    """At logN=16 the pinned evaluation keys alone exceed the 256 MiB the
    cache used to default to — the cause of the startup crash."""
    from repro.compiler import PassConfig
    from repro.core.params import paper_params_bootstrap
    from repro.core.trace import evk_bytes
    from repro.runtime.ciphertext_backend import CiphertextBackend
    params = paper_params_bootstrap()
    traces = sf.workload_traces(20, PassConfig())
    assert set(traces) == set(sf.WORKLOADS)
    n_keys, nbytes = CiphertextBackend.pinned_key_bytes(
        traces.values(), params, PassConfig())
    assert nbytes == n_keys * evk_bytes(params)
    assert n_keys >= 3                      # relin + Galois keys
    assert nbytes > sf.CONST_CACHE_MB * 2 ** 20


def test_ciphertext_paper_params_refuse_a_cpu(capsys):
    """Without --smoke the ciphertext backend runs logN=16 on a TPU, and
    says so instead of silently running on the CPU."""
    if jax.default_backend() == "tpu":
        pytest.skip("a TPU is attached")
    with pytest.raises(SystemExit) as ei:
        sf.main(["--backend", "ciphertext"])
    assert ei.value.code == 2
    assert "TPU" in capsys.readouterr().err


def test_compile_cache_follows_env(monkeypatch, tmp_path):
    monkeypatch.setenv(jax_cache.ENV, str(tmp_path))
    assert jax_cache.cache_dir() == str(tmp_path)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(jax_cache.ENV, raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert jax_cache.cache_dir() == os.path.join(root, ".jax_cache")


@pytest.mark.parametrize("env", [True, False], ids=["env", "checkout"])
def test_enable_compile_cache_sets_that_directory(monkeypatch, tmp_path,
                                                  env):
    """On an accelerator the helper points JAX at cache_dir(); on the
    CPU it leaves the cache off."""
    if env:
        monkeypatch.setenv(jax_cache.ENV, str(tmp_path))
    else:
        monkeypatch.delenv(jax_cache.ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert jax_cache.enable_compile_cache() == ""
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        path = jax_cache.enable_compile_cache()
        assert path == jax_cache.cache_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
