"""CLI launchers: solve.py end-to-end, the refresh journal, one dry-run
cell, examples."""
import json
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _run(args, timeout=560, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    return subprocess.run(
        [sys.executable] + args, env=env, capture_output=True, text=True,
        timeout=timeout, cwd=str(cwd or REPO))


def test_solve_cli():
    out = _run(["-m", "repro.launch.solve", "--n", "50000", "--k", "8",
                "--max-iters", "20"])
    assert out.returncode == 0, out.stdout + out.stderr
    lines = dict(l.split(": ") for l in out.stdout.strip().splitlines())
    assert int(lines["iterations"]) <= 20
    assert float(lines["max_violation"]) <= 1e-4
    gap = float(lines["duality_gap"])
    assert 0 <= gap < 0.01 * float(lines["primal"])


def test_refresh_cli_obs_journal(tmp_path):
    """A 2-generation refresh with ``--obs`` leaves a span journal that
    ``trace_view.py`` reads with the refresh's phases in it."""
    root = tmp_path / "root"
    out = _run(["-m", "repro.launch.refresh", "--users", "8192",
                "--chunk", "512", "--generations", "2", "--lookups", "256",
                "--obs", "--root", str(root)])
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    view = _run(["tools/trace_view.py", str(root), "--assert-phases",
                 "refresh.prepare,ckpt.save,refresh.publish"])
    assert view.returncode == 0, view.stdout + view.stderr
    assert "all 3 asserted phases present" in view.stdout


@pytest.mark.slow
def test_dryrun_single_cell_cli(tmp_path):
    out = _run(["-m", "repro.launch.dryrun", "--arch", "mamba2-370m",
                "--shape", "decode_32k", "--no-probe",
                "--out", str(tmp_path / "r.json")])
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    rec = json.load(open(tmp_path / "r.json"))[0]
    assert rec["status"] == "ok"
    assert rec["cost"]["flops"] > 0
    assert rec["memory"]["fits_16gb_hbm"]


def test_quickstart_example():
    out = _run(["examples/quickstart.py"])
    assert out.returncode == 0, out.stderr
    assert "duality gap" in out.stdout
    # feasible
    viol_line = [l for l in out.stdout.splitlines() if "max violation" in l][0]
    assert float(viol_line.split(":")[1].split("%")[0]) <= 1e-3
