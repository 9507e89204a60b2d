"""``chip_smoke.py``'s phases at tiny sizes on the CPU, kernels interpreted.

The script itself refuses to run off a TPU; these tests keep its control
flow, its reference checks and its failure paths under the tier-1 suite.
Routing is steered as on the chip: the kernel routes get their ``pallas``
cost factors, and a small VMEM budget sends the 'large' instances past the
resident kernels' gates, as deployment sizes do under the real budget.
"""
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(read_lens=(4, 6), band=2, pairs_per_len=4, nw_shape=(8, 12),
            mcm_small=6, mcm_small_count=2, mcm_large=32, mcm_large_count=2,
            edit_len=5, edit_count=2, knapsack_capacity=900,
            knapsack_items=4, knapsack_count=2)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod          # dataclasses resolve through it
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def chip_like_routing(monkeypatch):
    import repro.kernels as kernels
    from repro.kernels import ops

    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "24000")
    monkeypatch.setattr(kernels, "_mode_factor", lambda: 0.5)
    monkeypatch.setattr(kernels, "_tiled_mode_factor", lambda: 0.6)
    # count only this test's kernel entries, not earlier tests' in the worker
    monkeypatch.setattr(ops, "ENTRY_COUNTS", {})


def test_one_chip_phase_serves_every_kernel_route(smoke, chip_like_routing):
    sizes = smoke.Sizes(**TINY)
    info = smoke.one_chip(sizes, seed=3)
    for leg in ("cold", "warm"):
        served = {k.split("/")[1] for k in info[leg]["routes"]}
        assert set(smoke.KERNEL_ROUTES) <= served, info[leg]["routes"]
    assert any(d["cold"] for d in info["cold"]["drains"])
    assert not any(d["cold"] for d in info["warm"]["drains"])
    counts = smoke.kernel_counters("interpret")
    smoke.check_counters(counts)


def test_reference_check_rejects_a_wrong_answer(smoke):
    """A result that differs from its reference, or a decoded solution that
    does not recompute to it, fails the smoke."""
    from types import SimpleNamespace

    req = smoke.Request("mcm", {"dims": np.array([2.0, 3.0, 4.0])}, True)
    good = SimpleNamespace(status="done", answer=24.0, solution=SimpleNamespace(
        solution={"tree": (0, 1)}))
    smoke.check_results([req], {0: good}, [24.0])
    with pytest.raises(smoke.SmokeFailure, match="reference"):
        smoke.check_results([req], {0: SimpleNamespace(
            status="done", answer=25.0, solution=good.solution)}, [24.0])
    wrong_tree = SimpleNamespace(status="done", answer=25.0,
                                 solution=good.solution)
    with pytest.raises(smoke.SmokeFailure, match="recomputes"):
        smoke.check_results([req], {0: wrong_tree}, [25.0])


def test_route_and_counter_checks_fail_when_a_route_is_missing(smoke):
    routes = {("mcm", r): 1 for r in smoke.KERNEL_ROUTES[1:]}
    with pytest.raises(smoke.SmokeFailure, match="kernel_blocked"):
        smoke.check_routes(routes)
    with pytest.raises(smoke.SmokeFailure, match="mcm_blocked"):
        smoke.check_counters({"mcm_blocked": 0.0, "grid_blocked": 2.0})


def test_main_refuses_the_cpu(smoke, capsys, monkeypatch):
    """Off a TPU the script exits with an error and prints no result."""
    import repro.jax_cache

    # tests never turn the persistent compilation cache on
    monkeypatch.setattr(repro.jax_cache, "use_persistent_cache",
                        lambda: "(off in tests)")
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
