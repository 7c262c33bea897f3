"""The rules that keep the program honest about the device it runs on
(PR 22), checked here on the CPU backend: where the compile cache
lives, that importing the package claims no chip, that asking for a TPU
without one fails loudly, and that a kernel whose lowering raises takes
the step down with it instead of falling back."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_IMPORTS = ("import jax, paddle_tpu, paddle_tpu.serving, "
            "paddle_tpu.distributed.launch\n"
            "from jax._src import xla_bridge\n"
            "print('CACHE', jax.config.jax_compilation_cache_dir)\n"
            "print('BACKENDS', sorted(xla_bridge._backends))\n")


@pytest.fixture(scope="module")
def fresh(tmp_path_factory):
    """Every fresh-process check of this module, started together (they
    are independent and each costs an interpreter start-up): name ->
    CompletedProcess."""
    def start(args, cwd=REPO, **env):
        base = {k: v for k, v in os.environ.items()
                if k not in ("JAX_COMPILATION_CACHE_DIR",
                             "PADDLE_TPU_TESTS_TPU")}
        base.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env)
        return subprocess.Popen(
            [sys.executable, *args], cwd=cwd, env=base, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)

    procs = {
        "imports": start(["-c", _IMPORTS]),
        "imports_elsewhere": start(
            ["-c", _IMPORTS], cwd=str(tmp_path_factory.mktemp("cwd"))),
        "imports_cache_env": start(
            ["-c", _IMPORTS], JAX_COMPILATION_CACHE_DIR="/x/placed"),
        "chip_smoke": start([os.path.join(REPO, "chip_smoke.py")]),
        "bench": start([os.path.join(REPO, "bench.py")]),
        "tpu_tier": start(
            ["-m", "pytest", "tests/test_tpu_tier.py", "-m", "tpu", "-q",
             "-p", "no:cacheprovider"], PADDLE_TPU_TESTS_TPU="1"),
    }
    done = {}
    for name, proc in procs.items():
        out, err = proc.communicate(timeout=180)
        done[name] = subprocess.CompletedProcess(
            proc.args, proc.returncode, out, err)
    return done


def _field(out, key):
    return next(line.split(" ", 1)[1] for line in out.splitlines()
                if line.startswith(key + " "))


def test_compile_cache_rule_and_backend_free_imports(fresh):
    """Without JAX_COMPILATION_CACHE_DIR two fresh processes, started
    from different directories, resolve the same absolute path under
    the checkout; with it set, the value stands and no code overrode
    it.  Importing the package, the server and the launcher initialises
    no backend in any of them."""
    outs = [fresh[k] for k in ("imports", "imports_elsewhere",
                               "imports_cache_env")]
    for r in outs:
        assert r.returncode == 0, r.stderr[-2000:]
        assert _field(r.stdout, "BACKENDS") == "[]"
    want = os.path.join(REPO, ".jax_cache")
    assert [_field(r.stdout, "CACHE") for r in outs] == \
        [want, want, "/x/placed"]


def test_chip_smoke_refuses_the_cpu_backend(fresh):
    r = fresh["chip_smoke"]
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_bench_refuses_the_cpu_backend(fresh):
    r = fresh["bench"]
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert "mfu" not in r.stdout.lower()


def test_tpu_tier_without_a_tpu_fails_the_session(fresh):
    r = fresh["tpu_tier"]
    assert r.returncode != 0
    assert "default JAX backend is 'cpu'" in r.stdout + r.stderr
    assert "skipped" not in r.stdout


def test_set_device_tpu_raises_without_a_tpu():
    import paddle_tpu as paddle

    before = jax.config.jax_platforms
    try:
        with pytest.raises(RuntimeError, match="'cpu'"):
            paddle.set_device("tpu")
        assert paddle.set_device("cpu") == "cpu:0"
    finally:
        jax.config.update("jax_platforms", before)


def _lm_loss():
    from paddle_tpu.ops import fused_loss

    x = jnp.ones((8, 128), jnp.float32)
    w = jnp.ones((256, 128), jnp.float32)
    return fused_loss.fused_linear_cross_entropy(
        x, w, jnp.zeros((8,), jnp.int32))


def _dequant():
    from paddle_tpu.ops import quant_ops

    return quant_ops.dequant_matmul(
        jnp.ones((8, 128)), jnp.ones((32, 128), jnp.int8), jnp.ones((32,)))


def _scaled():
    from paddle_tpu.ops import lowp

    return lowp.scaled_matmul(jnp.ones((8, 128)), jnp.ones((128, 128)),
                              qdtype="int8")


def _conv():
    from paddle_tpu.ops import nn_ops

    return nn_ops.conv2d(jnp.ones((1, 16, 8, 8)), jnp.ones((16, 16, 3, 3)),
                         padding=1)


@pytest.mark.parametrize("module,env,call", [
    ("fused_loss", "PADDLE_TPU_LMLOSS_FORCE", _lm_loss),
    ("quant_ops", "PADDLE_TPU_QUANT_FORCE", _dequant),
    ("lowp", "PADDLE_TPU_LOWP_FORCE", _scaled),
    ("fused_conv", "PADDLE_TPU_CONV_FORCE", _conv),
])
def test_kernel_that_fails_to_lower_raises(module, env, call, monkeypatch,
                                           recwarn):
    """With the kernel selected, a lowering error is the caller's error:
    no probe, no warning, no quiet switch to the lax twin."""
    import importlib

    mod = importlib.import_module(f"paddle_tpu.ops.{module}")
    assert not hasattr(mod, "_probe") and not hasattr(mod, "_probe_result")

    def refuse(*a, **k):
        raise NotImplementedError("Mosaic refuses this lowering")

    monkeypatch.setattr(mod.pl, "pallas_call", refuse)
    monkeypatch.setenv(env, "pallas")
    with pytest.raises(NotImplementedError, match="Mosaic refuses"):
        call()
    assert not [w for w in recwarn.list if "path" in str(w.message)]
    monkeypatch.setenv(env, "lax")
    assert np.isfinite(np.asarray(call())).all()


def test_native_build_failure_is_reported_not_swallowed(monkeypatch):
    """No compiler is a supported machine; a compile that FAILED is a
    defect chip_smoke.py reports through native.build_errors()."""
    from paddle_tpu import native

    def fails(*a, **k):
        raise subprocess.CalledProcessError(
            1, ["g++"], stderr="datafeed.cc:1: error: boom")

    def absent(*a, **k):
        raise FileNotFoundError("g++")

    for attr in ("_lib", "_build_error", "_ps_lib", "_ps_build_error"):
        monkeypatch.setattr(native, attr, None)
    monkeypatch.setattr(native, "_compile", fails)
    assert not native.available() and native.ps_table_lib() is None
    errs = native.build_errors()
    assert set(errs) == {"datafeed", "ps_table"}
    assert "error: boom" in errs["datafeed"]
    np.testing.assert_array_equal(       # the numpy path still serves
        native.gather_rows(np.arange(6.0, dtype=np.float32), [4, 1]),
        [4.0, 1.0])

    for attr in ("_lib", "_build_error", "_ps_lib", "_ps_build_error"):
        monkeypatch.setattr(native, attr, None)
    monkeypatch.setattr(native, "_compile", absent)
    assert not native.available() and native.ps_table_lib() is None
    assert native.build_errors() == {}
