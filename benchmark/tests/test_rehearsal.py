"""One CPU rehearsal of run.py per traffic file: the body runs to the end at
SF0.01, the served answers agree with the plain references, the result has
the contract's shape — and the exit is still non-zero, because a CPU run is
never a result."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest

MAN = manifest.Manifest()
CELLS = {w["traffic"]: w["name"] for w in MAN.doc["workloads"]}


def rehearse(cell: str, trace: int, *extra) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", str(trace), *extra],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)


@pytest.mark.parametrize("traffic", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal(traffic, trace):
    cell = CELLS[traffic]
    p = rehearse(cell, trace, "--rehearse-sf", "0.01")
    assert p.returncode == 1, p.stderr[-2000:]
    marked = [ln for ln in p.stderr.splitlines()
              if ln.startswith("REHEARSAL on cpu (not a result): ")]
    assert len(marked) == 1, p.stderr[-2000:]
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    result = json.loads(marked[0].split(": ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    group = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in MAN.metrics(group, cell)}
    assert set(result["metrics"]) <= names
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] > result["device"]["busy_s"]
        assert len(result["breakdown"]["device_ops"]) <= 10
        assert len(result["breakdown"]["idle_gaps"]) <= 10
    else:
        assert set(result["metrics"]) == names
        assert "samples: n=" in p.stdout
    assert "device: platform=cpu" in p.stdout


def test_no_tpu_is_a_failure_with_no_result():
    p = rehearse(next(iter(CELLS.values())), 0)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
