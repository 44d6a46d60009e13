"""A run with the timed path broken underneath it comes out not correct.

Each test drives a whole run of a cell on the CPU, past the harness's look
for a chip, with one fault planted in the program: once for each fault the
cell can have. A run of the unbroken program comes out correct."""
import contextlib
import io
import json
import subprocess
import sys

import numpy as np
import pytest

from perfbench import core, run

SECONDS = "2"


def _run(workload: str, seed: int = 2**31 + 11) -> dict:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", workload, "--seed", str(seed), "--seconds", SECONDS,
                         "--trace", "0"]) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def _on_cpu(monkeypatch, tmp_path):
    """Past the harness's look for a chip, with no persistent compile cache."""
    import jax

    monkeypatch.setattr(core, "check_devices", lambda chips: jax.devices())
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))


def _crms_returning(monkeypatch, alter):
    from repro.api import policies

    real = policies.crms

    def broken(apps, caps, alpha, beta, **kw):
        _LAST_CALL[0] = (apps, caps, alpha, beta)
        return alter(real(apps, caps, alpha, beta, **kw), kw.get("warm"))

    monkeypatch.setattr(policies, "crms", broken)


def _stale(alloc, warm):
    """A re-plan that hands back the state it started from."""
    return warm if warm is not None else alloc


def _altered(alloc, warm):
    """An answer altered where it is produced: one quota a millionth off."""
    alloc.r_cpu = alloc.r_cpu.copy()
    alloc.r_cpu[0] *= 1.0 + 1e-6
    return alloc


def _perturbed(alloc, warm):
    """Quotas off their optimum by a ten-thousandth, and the allocation then
    evaluated anew, so that its Ws and objective agree with its quotas."""
    from repro.core.problem import evaluate

    apps, caps, alpha, beta = _LAST_CALL[0]
    c = np.asarray(alloc.r_cpu, dtype=float) * (1.0 - 1e-4)
    out = evaluate(apps, alloc.n, c, alloc.r_mem, caps, alpha, beta)
    out.meta = alloc.meta
    return out


_LAST_CALL = [None]


def _scan_returning(monkeypatch, alter):
    from repro.core import des_vector

    real = des_vector.segment_scan

    def broken(W0, smask, gaps, svcs, valid, backend="jax"):
        W, waits = real(W0, smask, gaps, svcs, valid, backend=backend)
        return alter(W0, W, waits)

    monkeypatch.setattr(des_vector, "segment_scan", broken)


def _stopping_early(monkeypatch):
    """A refinement that stops after its first iteration."""
    import dataclasses

    from repro.api import policies

    real = policies.crms

    def broken(apps, caps, alpha, beta, **kw):
        kw["options"] = dataclasses.replace(kw["options"], max_refine_iters=1)
        return real(apps, caps, alpha, beta, **kw)

    monkeypatch.setattr(policies, "crms", broken)


REPLAN, VALIDATE = "paper_sufficient.replan", "paper_node.validate"


@pytest.mark.usefixtures("_on_cpu")
@pytest.mark.parametrize("workload", [REPLAN, VALIDATE])
def test_the_unbroken_program_is_correct(workload):
    res = _run(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


@pytest.mark.usefixtures("_on_cpu")
@pytest.mark.parametrize("fault", [_stale, _altered, _perturbed],
                         ids=["state_unchanged", "answer_altered", "quotas_perturbed"])
def test_replan_fault_is_caught(monkeypatch, fault):
    _crms_returning(monkeypatch, fault)
    res = _run(REPLAN)
    assert not res["correct"], res["checks"]


@pytest.mark.usefixtures("_on_cpu")
def test_refinement_stopping_early_is_caught(monkeypatch):
    _stopping_early(monkeypatch)
    res = _run(REPLAN)
    assert not res["correct"], res["checks"]
    assert res["checks"]["objective_gap"]["value"] > res["checks"]["objective_gap"]["limit"]


@pytest.mark.usefixtures("_on_cpu")
@pytest.mark.parametrize("fault", [
    lambda W0, W, waits: (W0, np.zeros_like(waits)),  # the scan leaves its state unchanged
    lambda W0, W, waits: (W, waits * (1.0 + 1e-4)),  # every wait altered where produced
], ids=["state_unchanged", "answer_altered"])
def test_validate_fault_is_caught(monkeypatch, fault):
    _scan_returning(monkeypatch, fault)
    res = _run(VALIDATE)
    assert not res["correct"], res["checks"]


def test_no_tpu_means_no_result(capsys):
    with pytest.raises(SystemExit) as exc:
        run.main(["--workload", REPLAN, "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil

    shutil.copy(core.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(core.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run([sys.executable, "perfbench/run.py", "--workload", REPLAN,
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
