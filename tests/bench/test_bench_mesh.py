"""CPU rehearsal of the mesh cell: its driver at a tiny size on four virtual
CPU devices (a child process, since JAX fixes the device count when it
starts), correct, and not correct with a member's state left unchanged
under the timed path; the forecast step's work count; and the sampled
checks drawn from the seed."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import harness
from bench.drivers import ensemble_mesh
from bench.work import forecast

ROOT = Path(__file__).resolve().parents[2]
CELL = "cosmo1e_l80_f64.mesh2x2"

_SCRIPT = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import repro  # noqa: F401  (float64 on, as run.py has it)
from bench import harness
from bench.drivers import ensemble_mesh

cell = harness.load_cell({cell!r})
cell.config["domain"] = [10, 6, 4]  # odd tiles, 5 x 3, on the 2x2 mesh
cell.config["members"] = 3
cell.control = {control!r}
seed = {seed!r}
if {fault!r}:
    from repro.ensemble.compile import DistributedEnsemble

    stuck = ensemble_mesh._samples(cell.traffic, 3, seed)[0][0]
    iterate = DistributedEnsemble.iterate

    def unchanged(self, n, fields, scalars=None, **kwargs):
        before = fields["phi"][stuck]
        out = iterate(self, n, fields, scalars, **kwargs)
        out["phi"] = out["phi"].at[stuck].set(before)
        return out

    DistributedEnsemble.iterate = unchanged


class Device:
    platform, device_kind = "cpu", "cpu"


rec = ensemble_mesh.run(cell, seed, 0.3, False)
print(json.dumps({{"line": harness.result(rec, False, Device()), "counters": rec.counters, "chips": rec.chips}}))
"""


def _run(seed: int, *, fault: bool = False, control: bool = False) -> dict:
    script = _SCRIPT.format(root=str(ROOT), src=str(ROOT / "src"), cell=CELL, seed=seed, fault=fault,
                            control=control)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(script)], capture_output=True, text=True,
                         timeout=600, env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_mesh_driver_is_correct_and_reports_its_metrics():
    out = _run(2**33 + 17)
    line, c = out["line"], out["counters"]
    assert line["correct"] and line["failed"] == 0 and out["chips"] == 4
    assert set(line["metrics"]) == {"mpts_per_s", "setup_s"} and set(line["checks"]) == {"forecast_err"}
    assert line["checks"]["forecast_err"]["value"] < 1e-13
    # at least to the later sampled step, in whole dispatches of 4 steps
    assert c["steps"] >= 8 and c["steps"] % 4 == 0 and c["calls"] == c["steps"] // 4 == line["attempted"]
    assert c["points"] == 10 * 6 * 4 * 3 and c["tile"] == [5, 3, 4] and c["members_per_chip"] == 3
    # two exchanges a step of one-point stripes: I (3 x 4), then J with the I ring (7 x 4)
    assert c["exchanges_per_step"] == 2 and c["exchange_bytes_per_step"] == 3 * 2 * 8 * (3 * 4 + 7 * 4)


def test_mesh_driver_with_a_member_left_unchanged_is_not_correct():
    line = _run(17, fault=True)["line"]
    assert line["correct"] is False
    assert line["checks"]["forecast_err"]["value"] > line["checks"]["forecast_err"]["limit"]


def test_mesh_control_fails_the_limit():
    """The float32 reference, read in the program's place on the same answers,
    lies above the limit that the program keeps under."""
    checks = _run(2**40 + 5, control=True)["line"]["checks"]
    assert checks["forecast_err"]["value"] < checks["forecast_err"]["limit"]
    assert checks["control.forecast_err"]["value"] > checks["control.forecast_err"]["limit"]


def test_mesh_samples_are_the_same_work_for_every_seed():
    traffic = harness.load_cell(CELL).traffic
    draws = [ensemble_mesh._samples(traffic, 11, seed) for seed in (1, 2**31 + 9, 2**40 + 3, 5, 6, 7)]
    for members, later in draws:
        assert len(members) == len(set(members)) == 2 and all(0 <= m < 11 for m in members)
        assert later in (8, 12)
    assert len({tuple(m) for m, _ in draws}) > 1 and {later for _, later in draws} == {8, 12}


def test_forecast_work_at_a_small_domain():
    assert forecast.ops((4, 5, 3)) == 19 * 60
    # phi read over (4+2) x (5+2) x 3; u and v read and phi written over 4 x 5 x 3; float64
    assert forecast.bytes_moved((4, 5, 3), 8) == 8 * (6 * 7 * 3 + 3 * 60)


def test_step_mfu_mesh_of_a_known_window():
    cell = harness.Cell(name="x", chips=4, config={}, traffic={}, end_to_end=[], per_layer=[])
    rec = harness.Record(cell=cell, window_s=2.0, device_kind="TPU v5 lite", chips=4)
    rec.counters.update(steps=8, tile=[5, 3, 4], members_per_chip=3, itemsize=8)
    read = harness.load_module("metrics", "step_mfu.mesh").read
    assert read(rec) == pytest.approx(100 * 3 * forecast.bytes_moved((5, 3, 4), 8) / 819e9 / 0.25)
    rec.counters.clear()
    assert read(rec) is None


#: a traced run of the cell's driver on a v5e 2x2 host at 128 x 96 x 80,
#: 11 members, three dispatches of four steps, with its counters
RECORDED = Path(__file__).resolve().parent / "data" / "v5e_mesh_128x96x80.xplane.pb"
RECORDED_COUNTERS = {"points": 128 * 96 * 80 * 11, "steps": 12, "calls": 3, "exchange_bytes_per_step": 1605120,
                     "exchanges_per_step": 2, "tile": [64, 48, 80], "members_per_chip": 11, "itemsize": 8}
RECORDED_WINDOW_S = 0.02603648800004521
MESH_READERS = ("exchange_share.mesh", "exchange_gbps.mesh", "idle_share.mesh", "step_mfu.mesh")


def _recorded(trace=True) -> harness.Record:
    from bench import trace as btrace

    cell = harness.Cell(name=CELL, chips=4, config={}, traffic={}, end_to_end=[], per_layer=[])
    rec = harness.Record(cell=cell, window_s=RECORDED_WINDOW_S, device_kind="TPU v5 lite", chips=4,
                         trace=btrace.load(RECORDED) if trace else None)
    rec.counters.update(RECORDED_COUNTERS)
    return rec


def test_a_mesh_trace_recorded_on_a_v5e_host():
    from bench import collectives

    rec = _recorded()
    t = rec.trace
    assert len(t.devices) == 4 and all(len(m) == 7 for m in t.modules)
    assert [n for _, _, n in t.host].count("ensemble.mesh_iterate") == 3  # one span a dispatch
    chips = collectives.per_chip(rec)
    assert len(chips) == 4
    for window, busy, coll in chips:
        assert 0 < coll < busy < window
    # the exchanges are collective-permutes, each split into a start and a done
    names = {n.split(" ")[0].rstrip(".0123456789") for ops in t.devices for _, _, n in ops
             if collectives.COLLECTIVE_OP.match(n)}
    assert names == {"%collective-permute-start", "%collective-permute-done"}


def test_mesh_readers_on_the_recorded_trace():
    rec = _recorded()
    got = {m: harness.load_module("metrics", m).read(rec) for m in MESH_READERS}
    assert got == pytest.approx({"exchange_share.mesh": 2.549448188992237, "exchange_gbps.mesh": 51.36316034086183,
                                 "idle_share.mesh": 43.48638447275445, "step_mfu.mesh": 4.958532396172059})
    assert all(0 < got[m] < 100 for m in ("exchange_share.mesh", "idle_share.mesh", "step_mfu.mesh"))


@pytest.mark.parametrize("reader", MESH_READERS[:3])
def test_mesh_trace_readers_read_nothing_without_a_trace_or_collectives(reader):
    """A run with no trace, or a trace whose chips ran no collective (a
    program without the exchanges), leaves the device-trace readers nothing
    to read; idle share still reads a trace without collectives."""
    read = harness.load_module("metrics", reader).read
    assert read(_recorded(trace=False)) is None
    rec = _recorded()
    rec.trace.devices = [[op for op in ops if "collective" not in op[2]] for ops in rec.trace.devices]
    assert (read(rec) is None) == (reader != "idle_share.mesh")
