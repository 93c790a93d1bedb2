"""The controls of the one-chip cells at a size a test run holds: the
reference in the precision below the configuration's, read in the
program's place on the same sampled answers, fails the limit that the
program meets."""

from __future__ import annotations

import pytest

from bench.drivers import dycore_step, serve_closed

from test_bench_drivers import tiny

CASES = {
    "dycore_l80_f32.step": (dycore_step, [16, 16, 12], {}),
    "forecast_l80_f64.ens11": (serve_closed, [12, 12, 6], {}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_control_fails_where_the_program_passes(name):
    driver, domain, traffic = CASES[name]
    cell = tiny(name, **traffic)
    cell.config["domain"] = domain
    cell.control = True
    checks = {c.name: c for c in driver.run(cell, 2**35 + 3, 0.5, False).checks}
    program = [c for n, c in checks.items() if not n.startswith("control.")]
    assert program and all(c.ok for c in program)
    for c in program:
        assert not checks[f"control.{c.name}"].ok, checks
