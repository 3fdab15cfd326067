"""One CPU rehearsal of run.py per traffic file: the body runs to the end at
SF0.01, the served answers agree with the plain references, the result has
the contract's shape — and the exit is still non-zero, because a CPU run is
never a result."""

import json
import os
import subprocess
import sys

import pytest

from harness import manifest, tail

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
    # every query's wall and the tail split, after the window, never in the
    # result: as many walls as right answers, every part summing to the wall
    lat = tail.parse_marked(p.stdout, tail.LATENCY_MARK)
    assert lat["n"] == len(lat["walls_s"]) == \
        result["attempted"] - result["failed"]
    assert all(0 <= i < lat["n"] for i in lat["beyond"])
    split = tail.parse_marked(p.stdout, tail.SPLIT_MARK)
    assert "refused" not in split and split["recorder_dropped"] == 0
    assert 1 <= split["held"] <= lat["n"]
    rest = split["rest"]
    assert rest["n"] >= 1 and rest["tasks_ms"] > 0 and rest["plan_ms"] > 0
    assert sum(rest[k + "_ms"] for k in tail.PARTS) == \
        pytest.approx(rest["wall_ms"])
    assert all(rest[k + "_ms"] > -5.0 for k in tail.PARTS)
    assert "latencies" not in marked[0] and "tail split" not in marked[0]


def test_no_tpu_is_a_failure_with_no_result():
    p = rehearse(next(iter(CELLS.values())), 0)
    assert p.returncode != 0
    assert "not a TPU" in p.stderr
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
