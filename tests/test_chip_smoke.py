"""``chip_smoke.py`` at a tiny scale on the CPU: its phases run in this
process in f32 (x64 off, as on the chip), against its numpy f64
references and their tolerances.  The kernel checks only lower the
programs here: ``jax.default_backend() == "cpu"`` keeps the plans on the
jnp oracle, so ``tpu_custom_call`` is looked for on the chip alone."""
import importlib.util
import os

import jax
import pytest

from repro.core import config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def world(smoke):
    """A small catalogue in f32, its numpy copy and a compile counter."""
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        _, db = smoke.setup(orders=400, seed=3)
        yield db, smoke.host(db), smoke.CompileCounter()
    finally:
        jax.config.update("jax_enable_x64", prev)


@pytest.fixture(scope="module")
def served(smoke, world):
    db, h, counter = world
    checks, (svc, first) = smoke.served(db, h, rounds=3, sweep=8,
                                        expect_kernels=False,
                                        compiles=counter)
    return checks, svc, first


def test_served_phase(served):
    checks = served[0]
    assert "round 1" in " ".join(checks) and "round 2" in " ".join(checks)
    assert "warm rounds 0 compiles" in checks
    for name in ("q1.sum.mean", "q3.cumulants.var", "q6.k2", "q18.prob",
                 "q20.prob", "sweep.var"):
        assert any(c.startswith(name + " ") for c in checks), name


def test_exact_phase(smoke, world, served):
    db, h, _ = world
    checks, _ = smoke.exact(db, h, served[1], count_orders=50,
                            exact_orders=64, expect_kernels=False)
    for name in ("count.pmf", "count.var", "per_order.total",
                 "per_order.mean"):
        assert any(c.startswith(name + " ") for c in checks), name


def test_streamed_phase(smoke, world, served):
    db, _, _ = world
    checks, out = smoke.streamed(db, served[1])
    assert "bit-equal to resident" in checks
    smoke.bit_equal("streamed vs served", out, served[2]["q6"])


def test_checks_fail_loudly(smoke):
    with pytest.raises(smoke.SmokeFailure):
        smoke.close("x", [1.0, 2.0], [1.0, 2.1], 0.01)
    with pytest.raises(smoke.SmokeFailure):
        smoke.bit_equal("x", {"a": jax.numpy.zeros(2)},
                        {"a": jax.numpy.ones(2)})


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_compile_cache_location(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert config.use_compile_cache() == os.path.join(ROOT,
                                                          ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", prev)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert config.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
