"""The manifest reader finds every file BENCHMARK.json names, and refuses a
name with a character outside letters, digits, '_', '.', '-'."""

import pytest

from harness import load, manifest


def test_every_named_file_exists():
    man = manifest.Manifest()
    files = man.files()
    assert files
    missing = [str(f) for f in files if not f.is_file()]
    assert not missing
    for w in man.doc["workloads"]:
        cfg = man.config(w["config"])
        assert set(cfg["_entry"]["reduced"]) <= set(cfg["reduced"])
        traffic = man.traffic(w["traffic"])
        load.check_traffic(traffic)
        for q in traffic["queries"]:
            assert man.query_sql(q["query"]).lower().startswith("select")
            ref = man.reference(q["query"])
            assert set(ref.COLUMNS) <= set(cfg["tables"])
            assert callable(ref.reference)
        for group in ("end_to_end", "per_layer"):
            for m in man.metrics(group, w["name"]):
                assert callable(man.metric_reader(group, m["name"]).read)


def test_per_cell_metrics_follow_the_workloads_key():
    man = manifest.Manifest()
    listed = {m["name"]: m["workloads"] for m in man.doc["end_to_end"]
              if "workloads" in m}
    for w in man.doc["workloads"]:
        got = {m["name"] for m in man.metrics("end_to_end", w["name"])}
        for name, cells in listed.items():
            assert (name in got) == (w["name"] in cells)


@pytest.mark.parametrize("bad", [
    "a b", "a/b", "../x", "a,b", "", "-a", ".a", "a" * 65, "q6;", "µs", None])
def test_bad_names_are_refused(bad):
    with pytest.raises(manifest.ManifestError):
        manifest.check_name(bad)
    man = manifest.Manifest()
    with pytest.raises(manifest.ManifestError):
        man.cell(bad)
    if isinstance(bad, str):
        with pytest.raises(manifest.ManifestError):
            man.query_sql(bad)


@pytest.mark.parametrize("good", ["q6", "sf0.25_q3_join", "tpch-sf10-1chip",
                                  "_x", "9", "a" * 64])
def test_good_names_pass(good):
    assert manifest.check_name(good) == good


def test_references_import_nothing_of_the_engine():
    man = manifest.Manifest()
    for f in man.files():
        if f.parent.name == "reference":
            assert "trino_tpu" not in f.read_text()


def test_seed_orders_the_same_block():
    traffic = {"queries": [{"query": "a", "weight": 3},
                           {"query": "b", "weight": 1}]}
    import itertools

    one = list(itertools.islice(load.sequence(traffic, 1, 0), 8))
    two = list(itertools.islice(load.sequence(traffic, 2**31 + 5, 0), 8))
    again = list(itertools.islice(load.sequence(traffic, 1, 0), 8))
    assert one == again
    for seq in (one, two):  # every block holds the same multiset
        assert sorted(seq[:4]) == sorted(seq[4:]) == ["a", "a", "a", "b"]
