"""The group-sparse loss cell that waits for admission: its entries
(``data/da320.loss.separated.json``, in the form ``BENCHMARK.json`` takes
them) name files that exist, its driver runs end to end at a size the CPU
holds, and a fault planted in what the timed path produces comes out not
correct.

The system's L-BFGS stops short in float32 (PERF.md, Open questions), so
these tests put a sound solver in its place, the plain reference's float32
solve, whose gradient with respect to the cost is its plan (Danskin), and
plant each fault on top of that.  The cell has no limits of its own until
sound runs of the system set them; ``LIMITS`` are this test's, at its size,
where the sound stand-in reads under a tenth of each (seeds 5-8:
``value_gap`` at most 5.2e-7, ``plan_l1`` 5.8e-5, ``marginal_rel`` 4.2e-4)
and each fault reads over one (the least: ``value_gap`` 4.4e-3,
``plan_l1`` 6.3e-3, ``marginal_rel`` 5.0e-2).
"""
import copy
import json

import jax
import jax.numpy as jnp
import pytest

import reference_gs as R
import run as bench_run
from drivers import gs_loss

NAME = "da320.loss.separated"
MANIFEST = bench_run.BENCH / "tests" / "data" / f"{NAME}.json"
TINY = {"num_classes": 16, "num_target": 160}
LIMITS = {"value_gap": 1e-5, "plan_l1": 1e-3, "marginal_rel": 1e-2,
          "empty_marginals": 0, "nonfinite": 0}


def _cell():
    """The cell as ``bench/run.py`` would load it from ``BENCHMARK.json``,
    cut to ``TINY``, with ``LIMITS``."""
    spec = json.loads(MANIFEST.read_text())
    c = copy.deepcopy(spec["workloads"][0])
    c["config_data"] = json.loads((bench_run.BENCH / "configs" / f"{c['config']}.json").read_text())
    c["config_data"].update(TINY)
    c["traffic_data"] = json.loads((bench_run.BENCH / "traffic" / f"{c['traffic']}.json").read_text())
    c["cell_data"] = {"limits": LIMITS}
    c["end_to_end"], c["per_layer"] = spec["end_to_end"], spec["per_layer"]
    return c


def test_manifest_names_files_that_exist():
    spec = json.loads(MANIFEST.read_text())
    (cfg,) = spec["configs"]
    (cell,) = spec["workloads"]
    assert (bench_run.ROOT / cfg["file"]).is_file()
    assert cell["config"] == cfg["name"]
    assert (bench_run.BENCH / "traffic" / f"{cell['traffic']}.json").is_file()
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert m["workloads"] == [NAME]
    for m in spec["per_layer"]:
        assert (bench_run.BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] == "gs_loss_step_s"


def _sound(cfg, gamma=None, max_iters=3000, alter=lambda value, T: (value, T)):
    """The reference's float32 solve in the system's place, as a loss whose
    gradient with respect to the cost is its plan (Danskin); ``gamma``
    solves another problem than the configuration's, ``max_iters`` stops
    the solve early, ``alter`` changes the answer where it is produced."""
    L, g = cfg["num_classes"], cfg["samples_per_class"]
    reg = R.from_rho(L, g, gamma or cfg["gamma"], cfg["rho"])

    def solve(C):
        m, n = C.shape
        a = jnp.full((m,), 1.0 / m, jnp.float32)
        b = jnp.full((n,), 1.0 / n, jnp.float32)
        sol = R.solve(C, a, b, 0.0, reg=reg, max_iters=max_iters)
        return alter(sol.value, sol.plan)

    @jax.custom_vjp
    def loss(C):
        return solve(C)[0]

    loss.defvjp(solve, lambda T, ct: (ct * T,))
    return loss


def _run(monkeypatch, seed=5, **fault):
    monkeypatch.setattr(gs_loss, "layer", lambda cfg, precision: _sound(cfg, **fault))
    r = bench_run.Run(_cell(), seed, 0.5, False, require_tpu=False)
    return bench_run.execute(r)


FAULTS = {
    "column_dropped": dict(alter=lambda v, T: (v, T.at[:, 0].set(0.0))),
    "plan_scaled": dict(alter=lambda v, T: (1.05 * v, 1.05 * T)),
    "wrong_gamma": dict(gamma=2.0),
    # the one-step stop of the system's L-BFGS ends solves after 8-10 iterations
    "stopped_early": dict(max_iters=10),
    "nonfinite": dict(alter=lambda v, T: (v, T.at[0, 0].set(jnp.nan))),
}


@pytest.mark.parametrize("seed", [5, 6])
def test_sound_stand_in_is_correct(monkeypatch, seed):
    res = _run(monkeypatch, seed)
    assert res["correct"], res["checks"]
    for name, c in res["checks"].items():
        assert c["value"] <= LIMITS[name] / 10
    assert res["facts"]["ref_residual"] <= R.F64_GTOL
    assert res["metrics"]["gs_loss_step_s"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(monkeypatch, fault):
    res = _run(monkeypatch, **FAULTS[fault])
    assert not res["correct"], res["checks"]
    if fault == "nonfinite":
        assert res["failed"] > 0


def test_traced_run_gives_the_solver_readers():
    """The system's own program at a CPU size: the solver and screening
    readers read its counts; the device readers find no device work on the
    CPU and report nothing, never 0."""
    r = bench_run.Run(_cell(), 7, 0.5, True, require_tpu=False)
    res = bench_run.execute(r)
    got = res["metrics"]
    for name in ("lbfgs_iters_per_step.gs_loss", "evals_per_iter.gs_loss",
                 "live_block_share.gs_loss"):
        assert got[name]["value"] > 0
    assert 0 < got["live_block_share.gs_loss"]["value"] <= 100
    assert "gradpsi_hbm_share.gs_loss" not in got


def test_least_bytes_lie_under_a_full_read():
    least = bench_run.reader("gradpsi_hbm_share.gs_loss").least_bytes
    m = n = 3200
    assert least(m, n, 10, 320 * 3200) == 4.0 * (m * n + 2 * (m + n))
    assert least(m, n, 10, 0) < least(m, n, 10, 1)


def test_device_readers_on_a_made_trace():
    """The three trace readers on a reduced trace made by hand for a v5e
    window of 10 steps, each one evaluation at the peak: 100% of it, and
    less where the kernels took longer."""
    import types

    import traces

    reader = bench_run.reader("gradpsi_hbm_share.gs_loss")
    r = bench_run.Run(_cell(), 1, 1.0, True, require_tpu=False)
    r.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    cfg = r.config
    g, n = cfg["samples_per_class"], cfg["num_target"]
    m = cfg["num_classes"] * g
    counts = {"evals": 1, "rounds": 2, "live_blocks": 2 * 100}
    r.facts.update(steps=10, window_solves=[(10, counts)])
    at_peak = 10 * reader.least_bytes(m, n, g, 100) / reader.HBM_BYTES_PER_S["TPU v5 lite"]

    def reduced(kernel_s):
        return traces.Reduced(busy_s=0.5, window_s=1.0, idle_share_pct=50.0,
                              breakdown={"device_ops": [["_compact_kernel", kernel_s],
                                                        ["fusion", 0.1]]})

    assert reader.read(r, reduced(at_peak)) == pytest.approx(100.0)
    assert reader.read(r, reduced(2 * at_peak)) == pytest.approx(50.0)
    assert bench_run.reader("device_ms_per_step.gs_loss").read(r, reduced(at_peak)) == 50.0
    assert bench_run.reader("idle_share.gs_loss").read(r, reduced(at_peak)) == 50.0
    r.devices = [types.SimpleNamespace(device_kind="TPU v4")]
    assert reader.read(r, reduced(at_peak)) is None
