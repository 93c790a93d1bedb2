"""CPU rehearsal of the one-chip drivers: each runs at a tiny size with the
Pallas kernels interpreted, called as a function past ``run.py``'s look for a
chip, and comes out correct; with its timed path broken underneath, the
same run comes out not correct."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.drivers import dycore_step, serve_closed

ROOT = Path(__file__).resolve().parents[2]


def tiny(name: str, **traffic) -> harness.Cell:
    cell = harness.load_cell(name)
    cell.config["domain"] = [8, 8, 6] if name.startswith("dycore") else [8, 8, 4]
    cell.traffic.update(traffic)
    return cell


class _Device:
    platform, device_kind = "cpu", "cpu"


def line(rec) -> dict:
    return harness.result(rec, False, _Device())


def test_dycore_step_is_correct_and_reports_its_metrics():
    out = line(dycore_step.run(tiny("dycore_l80_f32.step"), 2**31 + 7, 0.5, False))
    assert out["correct"] and out["attempted"] >= 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"mpts_per_s", "setup_s"}
    assert list(out)[-1] == "checks" and set(out["checks"]) == {"hdiff_err", "vadv_err"}
    assert all(c["value"] < c["limit"] for c in out["checks"].values())


def test_dycore_samples_are_the_same_work_for_every_seed():
    traffic = harness.load_cell("dycore_l80_f32.step").traffic
    draws = [dycore_step._samples(traffic, 11, seed) for seed in (1, 2**31 + 9, 2**40 + 3)]
    for d in draws:
        steps = sorted(n for n, _ in d)
        assert len(d) == traffic["check_steps"] and steps[0] == 1 and len(set(steps)) == len(steps)
        assert steps[-1] <= traffic["check_within"] and all(0 <= f < 11 for _, f in d)
    assert draws[0] != draws[1]


def test_serve_closed_is_correct_and_reports_its_metrics():
    out = line(serve_closed.run(tiny("forecast_l80_f64.ens11"), 2**40 + 1, 0.5, False))
    assert out["correct"] and out["attempted"] >= 11 and out["failed"] == 0
    assert set(out["metrics"]) == {"req_per_s", "setup_s"}


def test_serve_closed_compares_every_answer_of_every_batch_size():
    """Every request sent in the window is answered and compared, whichever
    batch it rode in: 11 members over batches of at most 8 fill one batch
    of 8 and pad 3 to 4, and the requests answered after the close count."""
    rec = serve_closed.run(tiny("forecast_l80_f64.ens11"), 2**33 + 5, 0.5, False)
    c = rec.counters
    assert c["completed"] == c["compared"] == rec.attempted > 11 and rec.failed == 0
    assert {8, 4} <= set(c["compared_by_members"])
    assert rec.window_s >= 0.5 and c["live_members"] == rec.attempted


# --- faults planted under the timed path ---------------------------------------


class _Unchanged:
    """A stencil whose call returns with its outputs as they were."""

    def __call__(self, *args, **kwargs):
        return None


def _perturbed(build):
    def wrapped(*args, **kwargs):
        st = build(*args, **kwargs)

        def call(*a, **k):
            st(*a, **k)
            out = a[1]  # hdiff(in_phi, out_phi): the answer, altered where it is produced
            out.data = out.data * (1 + 1e-3)

        return call

    return wrapped


def _unchanged_iterate(self, n, *args, **kwargs):
    return None


def _altered_gather(original):
    def gather(batched, m):
        return original(batched, m) * (1 + 1e-6)

    return gather


FAULTS = {
    "dycore-state-unchanged": ("dycore_l80_f32.step", "repro.stencils.vadv", "build_vadv",
                               lambda orig: (lambda *a, **k: _Unchanged())),
    "dycore-answer-altered": ("dycore_l80_f32.step", "repro.stencils.hdiff", "build_hdiff", _perturbed),
    "ens11-state-unchanged": ("forecast_l80_f64.ens11", "repro.ensemble.compile", "Ensemble.iterate",
                              lambda orig: _unchanged_iterate),
    "ens11-answer-altered": ("forecast_l80_f64.ens11", "repro.ensemble.batch", "gather_member",
                             _altered_gather),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    import importlib

    name, module, attr, make = FAULTS[fault]
    target = importlib.import_module(module)
    owner, _, leaf = attr.rpartition(".")
    if owner:
        target = getattr(target, owner)
    monkeypatch.setattr(target, leaf, make(getattr(target, leaf)))
    cell = tiny(name)
    driver = {"dycore_step": dycore_step, "serve_closed": serve_closed}[cell.driver]
    out = line(driver.run(cell, 17, 0.5, False))
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


# --- run.py itself ---------------------------------------------------------------


def _run_py(cwd: Path, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "dycore_l80_f32.step", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_exits_nonzero_without_a_tpu():
    res = _run_py(ROOT)
    assert res.returncode == 2, res.stderr[-2000:]
    assert res.stdout.strip() == "" and "not a TPU" in res.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_py(tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""
