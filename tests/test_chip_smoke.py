"""chip_smoke.py's contract, as far as a CPU can check it: it refuses to
run off-TPU, a leg that raises ends the run non-zero with no result line,
and the rehearsal flag drives the same legs at toy size."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(*args: str, code: str | None = None):
    cmd = [sys.executable, "-c", code] if code else [
        sys.executable, str(ROOT / "chip_smoke.py"), *args
    ]
    return subprocess.run(
        cmd, cwd=ROOT, capture_output=True, text=True, timeout=600
    )


def _has_result_line(stdout: str) -> bool:
    last = stdout.strip().splitlines()[-1] if stdout.strip() else ""
    try:
        return "ok" in json.loads(last)
    except ValueError:
        return False


def test_refuses_to_run_without_a_tpu():
    out = _run()
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert "platform=cpu" in out.stdout
    assert "] ..." not in out.stdout, "a leg started"
    assert not _has_result_line(out.stdout)


def test_rehearsal_runs_the_kernels_leg():
    out = _run("--rehearse", "--legs", "kernels")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "kernel vs f32 reference" in out.stdout
    # a rehearsal is not a chip run: no result line
    assert not _has_result_line(out.stdout)


def test_rehearsal_runs_the_features_leg():
    """Every optional serving feature on against off, at toy size: each
    is really on (its own check says so), is compared on the logits its
    tokens were drawn from, and the figures are left for the record."""
    out = _run("--rehearse", "--legs", "features")
    assert out.returncode == 0, out.stdout + out.stderr
    for feature in (
        "prefix_cache full hit", "prefix_cache partial hit",
        "batch_admission", "chunked_replay", "piggyback", "paged",
        "sampling_surface", "lora adapter 0", "KV wire",
    ):
        assert f"ok: {feature}: on against off over" in out.stdout, feature
    assert "the cache served one full and one partial hit" in out.stdout
    assert "four same-bucket prompts were admitted in one program" \
        in out.stdout
    figures = json.loads(
        (ROOT / "chiprun_out" / "features_rehearsal.json").read_text()
    )
    assert len(figures) == 9
    # the five that only reschedule are bitwise on XLA:CPU
    assert {f["feature"] for f in figures
            if f["rows_bitwise"] == f["rows"]} >= {
        "piggyback", "paged", "sampling_surface", "lora adapter 0",
        "KV wire",
    }
    assert not _has_result_line(out.stdout)


def test_a_raising_leg_fails_the_run():
    out = _run(code=(
        "import sys, chip_smoke\n"
        "def boom(*a): raise RuntimeError('injected failure')\n"
        "chip_smoke.kernels_leg = boom\n"
        "sys.exit(chip_smoke.main(['--rehearse', '--legs', 'kernels']))\n"
    ))
    assert out.returncode != 0
    assert "injected failure" in out.stderr
    assert "rehearsal finished" not in out.stdout
    assert not _has_result_line(out.stdout)


@pytest.mark.slow
def test_rehearsal_runs_serve_and_train():
    out = _run("--rehearse", "--legs", "serve,train")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "identical streams" in out.stdout
    assert "0 compile requests after warm-up" in out.stdout
    assert "falling losses" in out.stdout
