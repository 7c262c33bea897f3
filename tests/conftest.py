"""Test config: force an 8-device virtual CPU mesh (the 'no real cluster'
fake backend — SURVEY.md §4) before jax initialises.

On-chip tier (VERDICT r3 item 2): `PADDLE_TPU_TESTS_TPU=1 pytest tests/
-m tpu` leaves the backend alone so the chip is used; only tpu-marked
tests run (everything else is skipped in that mode).  Asking for that
tier on a machine without a TPU fails the session at start — it never
skips itself green.  Without the variable the tpu-marked tests skip."""

import os

TPU_MODE = os.environ.get("PADDLE_TPU_TESTS_TPU") == "1"

if not TPU_MODE:
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

if not TPU_MODE:
    jax.config.update("jax_platforms", "cpu")
    # thousands of sub-second CPU compiles: the persistent cache would
    # hash every one of them and keep almost none (tests of the cache
    # rule itself run in fresh processes — test_chip_rules.py)
    jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

# -- suite tiers (ref unittests/CMakeLists.txt DIST/EXCLUSIVE/NIGHTLY
# labels): `-m smoke` < 2 min core loop; `-m dist` = multi-device /
# multi-process; everything else is the full tier. Markers attach by
# module so new tests inherit a tier automatically.
_SMOKE_MODULES = {
    "test_ops_math", "test_autograd", "test_advice_r1", "test_advice_r2",
    "test_dy2static", "test_selected_rows", "test_optimizer",
    "test_static", "test_controlflow_pylayer", "test_nn_layers",
    "test_asp_dgc", "test_fs_metrics_opversion", "test_beam_search",
}
_DIST_MODULES = {
    "test_multichip_sweep", "test_distributed_parallel",
    "test_pipeline_schedule", "test_launch", "test_zero2_lars",
    "test_zero3_offload", "test_context_parallel",
    "test_parameter_server", "test_strategies_compiled",
    "test_heter_ps", "test_flash_gspmd", "test_pipeline_hetero",
    "test_memory_stats", "test_overlap", "test_serving_mesh",
}


def pytest_configure(config):
    if TPU_MODE and jax.default_backend() != "tpu":
        pytest.exit(
            "PADDLE_TPU_TESTS_TPU=1 asks for the on-chip tier but the "
            f"default JAX backend is {jax.default_backend()!r}",
            returncode=1)
    config.addinivalue_line("markers", "smoke: fast core tier (<2 min)")
    config.addinivalue_line("markers", "dist: multi-device/process tier")
    config.addinivalue_line("markers", "full: everything else")
    config.addinivalue_line(
        "markers", "tpu: real-chip tier (PADDLE_TPU_TESTS_TPU=1 -m tpu)")
    config.addinivalue_line(
        "markers", "slow: forks real processes / long wall-clock; "
        "excluded from tier-1 (-m 'not slow'); fast in-process "
        "equivalents of each scenario live in tier-1")


def pytest_collection_modifyitems(items):
    tiers = {"smoke", "dist", "full", "tpu"}
    for item in items:
        is_tpu = any(m.name == "tpu" for m in item.iter_markers())
        if TPU_MODE and not is_tpu:
            # chip runs execute ONLY the tpu tier — the CPU-mesh suite
            # assumes 8 virtual devices this backend doesn't have
            item.add_marker(pytest.mark.skip(
                reason="non-tpu test in PADDLE_TPU_TESTS_TPU mode"))
            continue
        if is_tpu and not TPU_MODE:
            item.add_marker(pytest.mark.skip(
                reason="on-chip tier: run with PADDLE_TPU_TESTS_TPU=1"))
            continue
        if any(m.name in tiers for m in item.iter_markers()):
            continue  # explicit per-test tier wins over the module tier
        mod = item.module.__name__.rsplit(".", 1)[-1]
        if mod in _SMOKE_MODULES:
            item.add_marker(pytest.mark.smoke)
        elif mod in _DIST_MODULES:
            item.add_marker(pytest.mark.dist)
        else:
            item.add_marker(pytest.mark.full)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle

    paddle.seed(1234)
    yield


@pytest.fixture()
def ps_runtime():
    """In-process PS server + sync trainer runtime (shared by the PS and
    heter-cache suites)."""
    from paddle_tpu.distributed import ps
    from paddle_tpu.distributed.ps.service import Communicator
    import paddle_tpu.distributed.ps.runtime as rtmod

    srv = ps.PSServer("127.0.0.1:0").start()
    eps = [f"127.0.0.1:{srv.port}"]
    client = ps.PSClient(eps)
    rm = ps.PSRoleMaker(server_endpoints=eps, role="TRAINER",
                        trainer_id=0, n_trainers=1)
    rt = ps.PSRuntime(rm, mode="sync")
    rt._client = client
    rt._communicator = Communicator(client, mode="sync").start()
    prev = getattr(rtmod, "_runtime", None)
    rtmod._runtime = rt
    yield rt
    rtmod._runtime = prev
    client.stop_servers()
    client.close()
    srv.stop()
