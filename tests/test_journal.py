"""Durable query journal (telemetry/journal.py): rotation bounds, torn-line
tolerance, the enriched QueryCompletedEvent round-trip, the
``system.runtime.query_history`` table, journal-seeded admission estimates
across a (subprocess-simulated) coordinator restart, and the
tools/lint_journal_schema.py contract."""

import json
import os
import subprocess
import sys

import pytest

from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.execution.resource_manager import estimate_peak_memory
from trino_tpu.runner import StandaloneQueryRunner
from trino_tpu.spi.eventlistener import QueryCompletedEvent
from trino_tpu.telemetry import journal
from trino_tpu.telemetry import runtime as rt

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_journal(tmp_path, monkeypatch):
    monkeypatch.setenv("TRINO_TPU_JOURNAL_DIR", str(tmp_path / "journal"))
    monkeypatch.delenv("TRINO_TPU_JOURNAL", raising=False)
    journal.reset_for_test()
    yield
    journal.reset_for_test()


def _completed(qid: str, sql: str = "SELECT 1", peak: int = 0,
               state: str = "FINISHED", **kw) -> QueryCompletedEvent:
    return QueryCompletedEvent(qid, sql, state=state, user="test",
                               peak_memory_bytes=peak, **kw)


# ----------------------------------------------------------- rotation bounds


def test_rotation_keeps_size_and_file_count_bounded(tmp_path):
    j = journal.QueryJournal(directory=str(tmp_path / "j"),
                            max_bytes=2048, max_files=2)
    for i in range(200):
        j.query_completed(_completed(f"q_{i}"))
    files = j.files()
    assert len(files) <= 3  # current + 2 rotated generations
    for f in files:
        # one record of slack: rotation triggers when a write would overflow
        assert os.path.getsize(f) <= 2048 + 600
    records = j.read()
    ids = [r["query_id"] for r in records]
    assert "q_199" in ids, "newest record must survive"
    assert "q_0" not in ids, "oldest generation must have been dropped"
    assert ids == sorted(ids, key=lambda s: int(s.split("_")[1])), \
        "read() must return records oldest-first"


def test_torn_tail_and_garbage_lines_are_skipped(tmp_path):
    j = journal.QueryJournal(directory=str(tmp_path / "j"))
    j.query_completed(_completed("q_good"))
    with open(j.path, "a", encoding="utf-8") as f:
        f.write("not json at all\n")
        f.write('{"schema": 1, "event": "query_completed", "query_id":')
    # the process crashed mid-write; the restarted journal must detect the
    # torn tail and not corrupt its first record by appending onto it
    j2 = journal.QueryJournal(directory=str(tmp_path / "j"))
    j2.query_completed(_completed("q_after"))
    ids = [r["query_id"] for r in j2.read()]
    assert ids == ["q_good", "q_after"]


def test_disabled_journal_returns_none(monkeypatch):
    monkeypatch.setenv("TRINO_TPU_JOURNAL", "0")
    journal.reset_for_test()
    assert journal.get_journal() is None
    assert journal.history() == []


# ------------------------------------------------- event listener round-trip


def test_completed_event_enrichment_round_trips(tmp_path):
    """The PR's QueryCompletedEvent additions — queued_time_ms,
    resource_group, speculative_wins, error_code — must survive the
    write/read cycle byte-for-byte."""
    j = journal.QueryJournal(directory=str(tmp_path / "j"))
    j.query_completed(_completed(
        "q_rt", sql="SELECT 2", peak=1 << 20, queued_time_ms=12.5,
        resource_group="global.etl", speculative_wins=3,
        wall_ms=99.0, cpu_ms=42.0, output_rows=7, input_rows=100,
        input_bytes=4096, retry_count=1))
    j.query_completed(_completed(
        "q_err", sql="SELECT 1/0", state="FAILED",
        error="division by zero", error_code="DIVISION_BY_ZERO"))
    ok, err = j.read(events=("query_completed",))
    assert ok["queued_time_ms"] == 12.5
    assert ok["resource_group"] == "global.etl"
    assert ok["speculative_wins"] == 3
    assert ok["retry_count"] == 1
    assert ok["fingerprint"] == rt.fingerprint("SELECT 2")
    assert ok["schema"] == journal.SCHEMA_VERSION
    assert err["state"] == "FAILED"
    assert err["error_code"] == "DIVISION_BY_ZERO"


def test_runner_writes_journal_and_classifies_failures():
    """End to end through the engine: FINISHED and FAILED queries both land
    in the journal, the failure with its spi/errors.py error code."""
    r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    r.execute("select count(*) from tpch.tiny.region")
    with pytest.raises(Exception):
        r.execute("select 1 / 0")
    recs = journal.history()
    by_state = {rec["state"]: rec for rec in recs}
    assert "FINISHED" in by_state and "FAILED" in by_state
    assert by_state["FINISHED"]["output_rows"] == 1
    assert by_state["FAILED"]["error_code"] == "DIVISION_BY_ZERO"
    created = journal.get_journal().read(events=("query_created",))
    assert len(created) == 2


# ------------------------------------- restart durability + admission seeding


_CHILD = r"""
import os
from trino_tpu.connectors.catalog import default_catalog
from trino_tpu.runner import StandaloneQueryRunner
from trino_tpu.spi.eventlistener import QueryCompletedEvent
from trino_tpu.telemetry import journal

r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
r.execute("select count(*) from tpch.tiny.region",
          query_id="q_pre_restart")
# a finished run of the estimator's target plan, with a real peak (CPU runs
# report no device watermark, so the peak is stamped via the listener path)
journal.get_journal().query_completed(QueryCompletedEvent(
    "q_heavy", "select * from big", state="FINISHED",
    peak_memory_bytes=7 << 20))
print("CHILD_OK")
"""


def test_restart_preserves_history_and_seeds_admission(tmp_path):
    """The acceptance scenario: a coordinator process runs queries and
    dies; the next process (this one) still lists them in
    system.runtime.query_history, and estimate_peak_memory returns the
    journal-seeded peak instead of the default."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               TRINO_TPU_JOURNAL_DIR=os.environ["TRINO_TPU_JOURNAL_DIR"])
    out = subprocess.run([sys.executable, "-c", _CHILD], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert "CHILD_OK" in out.stdout, out.stderr[-2000:]

    # "restarted coordinator": fresh singleton + seed cache in this process
    journal.reset_for_test()
    r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    rows = r.execute(
        "select query_id, state, output_rows from "
        "system.runtime.query_history").rows()
    assert ("q_pre_restart", "FINISHED", 1) in [tuple(x) for x in rows]

    fp = rt.fingerprint("select * from big")
    assert all(q.fingerprint != fp for q in rt.queries()), \
        "estimator must have no in-memory history for this fingerprint"
    default = 64 << 20
    assert estimate_peak_memory(fp, default) == 7 << 20
    assert estimate_peak_memory("fp_unknown", default) == default


def test_query_history_table_maps_all_columns(tmp_path):
    j = journal.get_journal()
    j.query_completed(_completed(
        "q_cols", sql="SELECT 3", peak=123, queued_time_ms=1.5,
        resource_group="global", speculative_wins=2, wall_ms=10.0,
        output_rows=4, input_rows=40, input_bytes=400))
    r = StandaloneQueryRunner(default_catalog(scale_factor=0.01))
    rows = r.execute(
        "select query_id, fingerprint, peak_memory_bytes, queued_time_ms, "
        "resource_group, speculative_wins, error_code "
        "from system.runtime.query_history where query_id = 'q_cols'").rows()
    assert [tuple(x) for x in rows] == [
        ("q_cols", rt.fingerprint("SELECT 3"), 123, 1.5, "global", 2, None)]


# ------------------------------------------------------------- schema lint


def test_journal_schema_lint_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "lint_journal_schema.py")],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stdout + out.stderr


def test_journal_schema_lint_catches_bad_record():
    from tools.lint_journal_schema import lint_record

    assert lint_record({"schema": journal.SCHEMA_VERSION,
                        "event": "query_completed", "ts": 1.0,
                        "query_id": "q"}) == []
    problems = lint_record({"event": "x", "ts": 1.0, "query_id": "q",
                            "stats": {"nested": True}})
    assert any("schema" in p for p in problems)
    assert any("nested" not in p and "stats" in p for p in problems)
    assert lint_record({"schema": journal.SCHEMA_VERSION, "event": "x",
                        "ts": float("nan"), "query_id": "q"})


# ------------------------------------------ satellite: fleet journal fold
def test_fleet_members_write_own_streams_and_readers_fold(tmp_path,
                                                          monkeypatch):
    """Each fleet member appends to its own ``query_journal-<node>.jsonl``
    stream (no cross-process rotation races); every reader folds ALL
    streams — including rotated generations — oldest-first per stream."""
    d = str(tmp_path / "fleet")
    monkeypatch.setenv("TRINO_TPU_HA_NODE_ID", "coordA")
    ja = journal.QueryJournal(directory=d)
    assert ja.path.endswith("query_journal-coordA.jsonl")
    ja.query_completed(_completed("q_a1", peak=1 << 20))
    monkeypatch.setenv("TRINO_TPU_HA_NODE_ID", "coordB")
    jb = journal.QueryJournal(directory=d, max_bytes=256, max_files=2)
    jb.query_completed(_completed("q_b1", peak=2 << 20))
    jb.query_completed(_completed("q_b2", peak=3 << 20))  # forces rotation
    monkeypatch.delenv("TRINO_TPU_HA_NODE_ID")
    jc = journal.QueryJournal(directory=d)  # legacy single-node name
    jc.query_completed(_completed("q_c1", peak=4 << 20))

    ids = {r["query_id"] for r in jc.read()}
    assert ids == {"q_a1", "q_b1", "q_b2", "q_c1"}, \
        "read() must fold every member's stream"
    assert ids == {r["query_id"] for r in ja.read()}, \
        "the fold is symmetric: A sees B and the legacy stream too"
    assert len(jc.fleet_files()) >= 4  # A + B current + B rotated + legacy


def test_peer_journal_append_invalidates_admission_seed(tmp_path,
                                                        monkeypatch):
    """The admission estimator's seed-cache signature covers the FLEET
    file set: a peak recorded by a PEER coordinator reaches this
    process's estimate without any restart."""
    monkeypatch.setenv("TRINO_TPU_JOURNAL_DIR", str(tmp_path / "fj"))
    journal.reset_for_test()
    me = journal.get_journal()
    assert me is not None
    fp = rt.fingerprint("select * from fleet_big")
    default = 64 << 20
    assert estimate_peak_memory(fp, default) == default

    # a peer (distinct node id -> distinct stream) lands a history record
    monkeypatch.setenv("TRINO_TPU_HA_NODE_ID", "coordPeer")
    peer = journal.QueryJournal(directory=me.directory)
    peer.query_completed(_completed("q_peer", sql="select * from fleet_big",
                                    peak=7 << 20))
    monkeypatch.delenv("TRINO_TPU_HA_NODE_ID")

    assert estimate_peak_memory(fp, default) == 7 << 20, \
        "the peer's append must invalidate the local seed cache"


# ------------------------------------------------- the follower (PR 35)


def _stats(j, qid: str, rows: int) -> None:
    j.plan_stats(qid, "sqlfp", {"fp": {"rows": rows}}, ts=1.0)


def _ids(records) -> list:
    return [rec["query_id"] for _, rec in records]


def test_follower_hands_out_each_appended_record_once(tmp_path):
    j = journal.QueryJournal(directory=str(tmp_path / "j"))
    follower = journal.JournalFollower(j, ("plan_stats",))
    assert follower.poll() == (True, [], 0), "no file yet: an empty start"
    assert follower.poll() is None
    _stats(j, "q_1", 1)
    j.query_completed(_completed("q_other"))  # not the followed type
    from_nothing, records, nbytes = follower.poll()
    assert from_nothing, "a file appeared: only a fresh start is right"
    assert _ids(records) == ["q_1"]
    assert records[0][0] == j.path, "a record names its stream"
    assert nbytes == os.path.getsize(j.path)
    assert follower.poll() is None, "nothing moved: a stat() and no read"
    size = os.path.getsize(j.path)
    _stats(j, "q_2", 2)
    _stats(j, "q_3", 3)
    from_nothing, records, nbytes = follower.poll()
    assert not from_nothing and _ids(records) == ["q_2", "q_3"]
    assert nbytes == os.path.getsize(j.path) - size, \
        "only the appended bytes are read"


def test_follower_leaves_a_torn_tail_until_its_newline(tmp_path):
    j = journal.QueryJournal(directory=str(tmp_path / "j"))
    follower = journal.JournalFollower(j, ("plan_stats",))
    _stats(j, "q_1", 1)
    follower.poll()
    line = json.dumps(journal._record_plan_stats(
        "q_torn", "sqlfp", {"fp": {"rows": 9}}, ts=2.0))
    with open(j.path, "a", encoding="utf-8") as f:
        f.write(line[:40])
    assert follower.poll() == (False, [], 40), "read, and not consumed"
    with open(j.path, "a", encoding="utf-8") as f:
        f.write(line[40:] + "\n" + "garbage\n")
    from_nothing, records, _ = follower.poll()
    assert not from_nothing and _ids(records) == ["q_torn"]
    assert follower.poll() is None


@pytest.mark.parametrize("event", ["rotation", "shrink", "replaced",
                                   "vanished", "peer_appears"])
def test_follower_starts_from_nothing_when_files_do_not_just_grow(
        event, tmp_path, monkeypatch):
    d = str(tmp_path / "j")
    j = journal.QueryJournal(directory=d, max_bytes=600, max_files=2)
    follower = journal.JournalFollower(j, ("plan_stats",))
    _stats(j, "q_1", 1)
    _stats(j, "q_2", 2)
    assert _ids(follower.poll()[1]) == ["q_1", "q_2"]
    expected = ["q_1", "q_2"]
    if event == "rotation":
        for i in range(3, 24):  # four records a file, three files kept
            _stats(j, f"q_{i}", i)
        expected = _ids((j.path, r) for r in j.read())
        assert "q_1" not in expected, "the oldest generation was dropped"
    elif event == "shrink":
        first = open(j.path, encoding="utf-8").readline()
        with open(j.path, "w", encoding="utf-8") as f:
            f.write(first)
        expected = ["q_1"]
    elif event == "replaced":
        text = open(j.path, encoding="utf-8").read()
        os.replace(j.path, j.path + ".old")
        with open(j.path, "w", encoding="utf-8") as f:
            f.write(text)  # the same bytes under another inode
        os.remove(j.path + ".old")
    elif event == "vanished":
        os.remove(j.path)
        expected = []
    elif event == "peer_appears":
        monkeypatch.setenv("TRINO_TPU_HA_NODE_ID", "peer")
        _stats(journal.QueryJournal(directory=d), "q_peer", 5)
        expected = ["q_peer", "q_1", "q_2"]  # streams in name order
    from_nothing, records, _ = follower.poll()
    assert from_nothing
    assert _ids(records) == expected
    assert follower.poll() is None


def test_follower_reads_a_growing_peer_stream_from_its_cursor(tmp_path,
                                                              monkeypatch):
    d = str(tmp_path / "j")
    mine = journal.QueryJournal(directory=d)
    monkeypatch.setenv("TRINO_TPU_HA_NODE_ID", "peer")
    peer = journal.QueryJournal(directory=d)
    _stats(mine, "q_mine", 1)
    _stats(peer, "q_peer_1", 2)
    follower = journal.JournalFollower(mine, ("plan_stats",))
    assert _ids(follower.poll()[1]) == ["q_peer_1", "q_mine"]
    _stats(peer, "q_peer_2", 3)
    from_nothing, records, nbytes = follower.poll()
    assert not from_nothing and records[0][0] == peer.path
    assert _ids(records) == ["q_peer_2"]
    assert 0 < nbytes < os.path.getsize(peer.path)
