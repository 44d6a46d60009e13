"""The float32 control fails the check that the program passes, at a size a
test run holds: the cells' own configurations, windows of 2 s, on the CPU."""
import contextlib
import io
import json

import pytest

from perfbench import control, core


@pytest.fixture(autouse=True)
def _on_cpu(monkeypatch, tmp_path):
    """Past the harness's look for a chip, with no persistent compile cache."""
    import jax

    monkeypatch.setattr(core, "check_devices", lambda chips: jax.devices())
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


@pytest.mark.parametrize("workload", ["paper_sufficient.replan", "paper_node.validate"])
def test_control_fails_where_the_program_passes(workload):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        control.main(["--workload", workload, "--seeds", "7,2147483659", "--seconds", "2"])
    for line in out.getvalue().strip().splitlines():
        r = json.loads(line)
        assert all(r["program"][k] <= r["limits"][k] for k in r["program"]), r
        assert any(r["control"][k] > r["limits"][k] for k in r["control"]), r
        print(r)
