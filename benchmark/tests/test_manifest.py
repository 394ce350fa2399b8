"""What `BENCHMARK.json`'s `per_layer` list must keep true, on the CPU,
with this directory's loader alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

(a) every entry has its file under `layer_metrics/`, lists cells that
exist, and every cell loads (`common.Cell` refuses a cell that reports a
layer metric without the end-to-end metric it moves); (b) the list is
under the contract's 128 and, as the tree stands after PR 47's fold,
under 60; (c) a cell that reports `serve_tokens_per_s` is named by the
four clocks no serve cell goes without, so that a cell cannot be added
blind; (d) no two entries that list the same cell have equal files and
fields (a twin: the next `benchmark` PR folds it, README.md); (e)
nothing a cell reported at PR 46 is lost: `per_layer_at_pr46.json`
holds every entry the manifest had then (its cells, its file's content,
the name its values stand under now), and every (cell, content) pair of
it is still given, under that name; (f) the cell that was refused 20 of
its metrics for want of room lists 26 or more.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE]

import common  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
AT_PR46 = os.path.join(HERE, "tests", "per_layer_at_pr46.json")
# the clocks every serve cell's traced run gives a value for
EVERY_SERVE_CELL = ("device_idle_share.rollout", "step_wait_ms.rollout",
                    "slot_occupancy.rollout", "compiles_in_window.rollout")
FIELDS = ("unit", "better", "source", "layer", "moves")


def _manifest():
    return common.load_json(MANIFEST)


def _file(name):
    return common.load_json(
        os.path.join(HERE, "layer_metrics", name + ".json"))


def _cells(m):
    return [w["name"] for w in m["workloads"]]


def _reports(m, metric):
    """The cells that report the end-to-end metric `metric`."""
    entry = next(e for e in m["end_to_end"] if e["name"] == metric)
    return set(entry.get("workloads", _cells(m)))


def _same_phases(spec):
    """`prefill_scatter` went with PR 26 and `phase_ms` sums what there
    is: a file that still named it reads what the one without it does."""
    spec = json.loads(json.dumps(spec))
    phases = spec.get("params", {}).get("phases")
    if phases:
        spec["params"]["phases"] = [p for p in phases
                                    if p != "prefill_scatter"]
    return json.dumps(spec, sort_keys=True)


@pytest.mark.parametrize("cell", _cells(_manifest()))
def test_cell_loads_with_its_metrics(cell):
    loaded = common.Cell(MANIFEST, cell)
    assert loaded.per_layer and "setup_s" in loaded.end_to_end
    assert len(loaded.end_to_end) >= 2


def test_every_entry_has_its_file_and_cells_that_exist():
    m = _manifest()
    cells = set(_cells(m))
    for e in m["per_layer"]:
        spec = _file(e["name"])
        assert {"reader", "layer", "moves", "unit"} <= set(spec), e["name"]
        assert [spec[k] for k in ("layer", "moves", "unit")] == \
            [e[k] for k in ("layer", "moves", "unit")], e["name"]
        assert e["workloads"] and set(e["workloads"]) <= cells, e["name"]
        # in the manifest's own order, each once
        assert e["workloads"] == [c for c in _cells(m)
                                  if c in set(e["workloads"])], e["name"]
        assert set(e["workloads"]) <= _reports(m, e["moves"]), e["name"]
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(HERE, "layer_metrics"))}
    assert {e["name"] for e in m["per_layer"]} <= on_disk


def test_the_list_has_room():
    n = len(_manifest()["per_layer"])
    assert n <= 128     # the contract's
    assert n <= 60      # the tree's, after PR 47's fold (57)


def test_no_serve_cell_is_blind():
    m = _manifest()
    by_name = {e["name"]: e for e in m["per_layer"]}
    serve = [c for c in _cells(m)
             if c in _reports(m, "serve_tokens_per_s")]
    assert len(serve) >= 6
    for name in EVERY_SERVE_CELL:
        assert by_name[name]["workloads"] == serve, name
    idle = by_name["device_idle_share.rollout"]
    train = by_name["device_idle_share.train"]
    assert idle["source"] == train["source"] == "device_trace"
    assert set(train["workloads"]) == _reports(m, "train_tokens_per_s")


def test_no_two_entries_of_a_cell_are_twins():
    m = _manifest()
    seen = {}
    for e in m["per_layer"]:
        key = (_same_phases(_file(e["name"])),) + tuple(
            e[k] for k in FIELDS)
        for cell in e["workloads"]:
            other = seen.setdefault((cell, key), e["name"])
            assert other == e["name"], (cell, other, e["name"])


def test_nothing_a_cell_reported_at_pr46_is_lost():
    m = _manifest()
    by_name = {e["name"]: e for e in m["per_layer"]}
    before, after = {}, {}
    for old, was in common.load_json(AT_PR46).items():
        now = by_name[was["now"]]
        assert _same_phases(_file(now["name"])) == _same_phases(
            was["file"]), (old, now["name"])
        assert set(was["cells"]) <= set(now["workloads"]), (old, now["name"])
        for cell in was["cells"]:
            before.setdefault(cell, set()).add(_same_phases(was["file"]))
    for e in m["per_layer"]:
        for cell in e["workloads"]:
            after.setdefault(cell, set()).add(_same_phases(_file(e["name"])))
    for cell in _cells(m):
        print(cell, "file contents at PR 46:", len(before[cell]),
              "now:", len(after[cell]))
        assert before[cell] <= after[cell], cell


def test_the_cell_that_had_no_room_reports_its_clocks():
    m = _manifest()
    mine = [e["name"] for e in m["per_layer"]
            if "lfm2_24b_serve_rollout_6k_s64" in e["workloads"]]
    assert len(mine) >= 26
    assert {"step_dispatch_ms.rollout", "step_fetch_ms.rollout",
            "prefill_device_ms.rollout", "prefill_fetch_ms.rollout",
            "prefill_ms_per_ktok.rollout", "device_starved_share",
            "export_s", "load_warm_s"} <= set(mine)
