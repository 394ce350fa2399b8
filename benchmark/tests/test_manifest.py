"""What `BENCHMARK.json`'s `per_layer` list must keep true, on the CPU,
with this directory's loader alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

(a) every entry has its file under `layer_metrics/`, lists cells that
exist, and every cell loads (`common.Cell` refuses a cell that reports a
layer metric without the end-to-end metric it moves); (b) the list is
under the contract's 128 and, as the tree stands after PR 52's fold (73),
under 80; (c) a cell that reports `serve_tokens_per_s` is named by the
five clocks no serve cell goes without (`EVERY_SERVE_CELL`, the whole
step's `serve_step_mfu` among them) and by every common clock
(`COMMON_CLOCKS`), so that a cell cannot be added blind: a cell-adding
PR APPENDS its cell's name to those entries' `workloads`, as
`serve_tokens_per_s`'s list takes it, and edits nothing else of an entry
(README.md, "What the next cell does"); (d) no two entries have equal
files and fields, within a cell or across cells (a twin); (e) nothing a
cell reported at PR 46 or at PR 51 is lost: `per_layer_at_pr46.json` and
`per_layer_at_pr51.json` hold every entry the manifest had then (its
cells, its file's content, the name its values stand under now), and
every (cell, content) pair of them is still given, under that name; (f)
the cell that was refused 20 of its metrics for want of room lists 26 or
more.
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
AT_PR51 = os.path.join(HERE, "tests", "per_layer_at_pr51.json")
# the clocks every serve cell's traced run gives a value for
EVERY_SERVE_CELL = ("device_idle_share.rollout", "step_wait_ms.rollout",
                    "slot_occupancy.rollout", "compiles_in_window.rollout",
                    "serve_step_mfu")
# and the rest of the common clocks: one entry each, whose `workloads` a
# new serve cell appends its name to
COMMON_CLOCKS = EVERY_SERVE_CELL + (
    "export_s", "load_warm_s", "warm_requests_s", "prefill_share.rollout",
    "device_starved_share", "step_dispatch_ms.rollout",
    "step_fetch_ms.rollout", "step_sched_ms.rollout",
    "prefill_device_ms.rollout", "prefill_fetch_ms.rollout",
    "seed_kv_ms.rollout", "prefill_ms_per_ktok.rollout",
    "starved_launch_ms", "starved_fetch_ms", "starved_sched_ms",
    "starved_admit_ms", "starved_loop_ms")
# the kinds whose bring-up times a check (`backlog`, the GPT-2 cell's,
# times none)
CHECK_CLOCK = "check_s.rollout"
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
    """A file's content, but for what reads the same whatever it says:
    `prefill_scatter` went with PR 26 and `phase_ms` sums what there
    is, so a file that still named it reads what the one without it
    does; and `expert_grouped_matmul`, the alternative PR 52 added to the
    expert rooflines' `match`, names a kernel no tree before it has."""
    spec = json.loads(json.dumps(spec))

    def clean(params):
        if "phases" in params:
            params["phases"] = [p for p in params["phases"]
                                if p != "prefill_scatter"]
        if "match" in params:
            params["match"] = [m for m in params["match"]
                               if m != "expert_grouped_matmul"]
        if isinstance(params.get("params"), dict):
            clean(params["params"])

    clean(spec.get("params", {}))
    return json.dumps(spec, sort_keys=True)


def _serve_cells(m):
    return [c for c in _cells(m) if c in _reports(m, "serve_tokens_per_s")]


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
    assert n <= 80      # the tree's, after PR 52's fold (73)


def test_no_serve_cell_is_blind():
    m = _manifest()
    by_name = {e["name"]: e for e in m["per_layer"]}
    serve = _serve_cells(m)
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


@pytest.mark.parametrize("cell", _serve_cells(_manifest()))
def test_the_cell_is_named_by_every_common_clock(cell):
    """Every common clock is ONE entry and names the cell: a cell that
    brought them under a suffix of its own (as PR 48's and PR 51's had
    to) would fail here twice, by the missing name and by the twin."""
    m = _manifest()
    by_name = {e["name"]: e for e in m["per_layer"]}
    for clock in COMMON_CLOCKS:
        entry = by_name[clock]
        assert cell in entry["workloads"], clock
        assert entry["moves"] in ("serve_tokens_per_s", "setup_s"), clock
    traffic = next(w["traffic"] for w in m["workloads"]
                   if w["name"] == cell)
    kind = common.load_json(os.path.join(
        HERE, "traffic", traffic + ".json"))["kind"]
    assert (cell in by_name[CHECK_CLOCK]["workloads"]) \
        == (kind != "backlog"), CHECK_CLOCK
    mine = {e["name"] for e in m["per_layer"] if cell in e["workloads"]}
    assert set(COMMON_CLOCKS) <= mine and len(mine) >= 23
    # no name of one architecture on a clock every cell reports
    stems = {n.split(".")[0] for n in COMMON_CLOCKS}
    assert not [n for n in mine - set(COMMON_CLOCKS) - {CHECK_CLOCK}
                if n.split(".")[0] in stems
                and n != "decode_step_ms.rollout"]


def test_no_two_entries_are_twins_across_cells():
    """What the fold removes: entries whose files are equal as JSON and
    whose five fields are equal are ONE entry (README.md, "Naming")."""
    seen = {}
    for e in _manifest()["per_layer"]:
        key = (_same_phases(_file(e["name"])),) + tuple(
            e[k] for k in FIELDS)
        assert seen.setdefault(key, e["name"]) == e["name"]


def test_the_whole_steps_share_is_one_entry():
    m = _manifest()
    entry = next(e for e in m["per_layer"] if e["name"] == "serve_step_mfu")
    assert entry["workloads"] == _serve_cells(m)
    assert (entry["unit"], entry["better"], entry["layer"],
            entry["moves"]) == ("%", "higher", "device",
                                "serve_tokens_per_s")
    assert not [e["name"] for e in m["per_layer"]
                if "mfu" in e["name"]
                and e["name"] not in ("serve_step_mfu", "train_mfu")]
    # every architecture's mapping has its module, and each module the
    # two functions the reader asks for
    modules = _file("serve_step_mfu")["params"]["modules"]
    for w in m["workloads"]:
        if w["name"] not in entry["workloads"]:
            continue
        config = next(c for c in m["configs"] if c["name"] == w["config"])
        harness = common.load_json(
            os.path.join(ROOT, config["file"])).get("harness") or {}
        name = harness.get("flops") or modules[harness.get("mapping", "")]
        module = __import__(name)
        assert callable(module.decode_least_bytes), name
        assert callable(module.pass_weight_bytes), name


@pytest.mark.parametrize("name", [
    "moe_expert_roofline.rollout", "moe_expert_roofline.expert_layers",
    "moe_expert_roofline.cmda", "moe_expert_roofline.nemotron3"])
def test_the_expert_rooflines_name_both_families(name):
    params = _file(name)["params"]
    params = params.get("params", params)
    assert params["match"] == ["ragged-dot", "expert_grouped_matmul"]
    assert params["exclude"] == ["metadata"]


def _nothing_is_lost(path):
    m = _manifest()
    by_name = {e["name"]: e for e in m["per_layer"]}
    before, after = {}, {}
    for old, was in common.load_json(path).items():
        was["cells"] = [c for c in was["cells"] if c in set(_cells(m))]
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
        print(cell, "file contents then:", len(before.get(cell, ())),
              "now:", len(after[cell]))
        assert before.get(cell, set()) <= after[cell], cell
    return before


def test_nothing_a_cell_reported_at_pr46_is_lost():
    _nothing_is_lost(AT_PR46)


def test_nothing_a_cell_reported_at_pr51_is_lost():
    """Every name the ledger's PR 51 lines hold, 116 of them, stands
    under a name of today's 73, for every cell that listed it."""
    m = _manifest()
    at51 = common.load_json(AT_PR51)
    assert len(at51) == 116
    before = _nothing_is_lost(AT_PR51)
    assert set(before) == set(_cells(m))
    names = {e["name"] for e in m["per_layer"]}
    assert {was["now"] for was in at51.values()} == names - {
        "serve_step_mfu"}
    gone = sorted(old for old, was in at51.items() if was["now"] != old)
    assert len(gone) == 44 and all(
        old.endswith((".phi4flash", ".nemotron3")) for old in gone)


def test_the_cell_that_had_no_room_reports_its_clocks():
    m = _manifest()
    mine = [e["name"] for e in m["per_layer"]
            if "lfm2_24b_serve_rollout_6k_s64" in e["workloads"]]
    assert len(mine) >= 26
    assert {"step_dispatch_ms.rollout", "step_fetch_ms.rollout",
            "prefill_device_ms.rollout", "prefill_fetch_ms.rollout",
            "prefill_ms_per_ktok.rollout", "device_starved_share",
            "export_s", "load_warm_s"} <= set(mine)
