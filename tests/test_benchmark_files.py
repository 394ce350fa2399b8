"""The benchmark's own tests (`benchmark/tests/`: the manifest's rules,
the backlogs' files, the readers' pricing; numpy and that directory's
loader alone, no JAX) collected into tier-1: the one file `PERF.md`
section 7 (PR 47 (a)) left for a PR of another kind. The modules are
loaded BY PATH and their cases re-exported under their module's name, so
each counts here as it does in `python3 -m pytest benchmark/tests`; none
is copied.

Three cases of `test_manifest.py` hold the list to the tree as PR 47's
fold left it (under 60 entries; the four clocks `EVERY_SERVE_CELL` names
list EVERY serve cell; every cell had entries at PR 46). A cell-adding PR
may not edit that file nor the folded entries' lists (`benchmark/
README.md`, "What the next cell does"), so a cell added since lists the
clocks under a suffix of its own: those three run here on the manifest
WITHOUT the cells added since, and `test_backlog_phi4flash.py` holds the
new cell's entries to the same rules. The next `benchmark` issue folds
the suffixes and brings the three up to date.

A cell's own file holds its entries to be the manifest's LAST (appended,
nothing put in the middle), which they were when it was written: such a
case runs here on the manifest without the cells added AFTER its cell
(`LAST_WHEN_WRITTEN`), and the newest cell's file holds the same of the
whole manifest.

A case that holds a cell, or the whole list, to an EXACT count of
entries cannot know the entries a later PR of another kind appends (a
`tracing` PR brings the metrics that read its records, and may not edit
a file of the benchmark): such a case runs here on the manifest without
those entries, by name (`ENTRIES_SINCE`). The next `benchmark` issue
moves the counts and drops this mask with the others.
"""

import functools
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_TESTS = os.path.join(HERE, "..", "benchmark", "tests")
MODULES = ("test_manifest", "test_backlogs", "test_backlog_lfm2",
           "test_expert_pricing", "test_backlog_phi4flash",
           "test_backlog_nemotron3", "test_backlog_minicpm_sala",
           "test_backlog_granite4", "test_train_mellum2",
           "test_backlog_glm5")
#: cells added after PR 47's fold, which `test_manifest.py` cannot know
SINCE_PR47 = ("phi4flash_serve_rollout_reason_s64",
              "nemotron3_nano_serve_rollout_reason_s128",
              "minicpm_sala_serve_rollout_32k",
              "granite4_h_micro_serve_rollout_reason_s48",
              "mellum2_12b_train_seq8k",
              "glm5_serve_rollout_12k_lsel")
#: (module, case): the cells added after the case's own cell, which it
#: holds to be the manifest's last
LAST_WHEN_WRITTEN = {
    ("test_backlog_phi4flash",
     "test_the_cell_lists_every_common_clock_under_its_suffix"):
    SINCE_PR47[1:],
    # the case holds the manifest's cells to be PR 51's nine
    ("test_manifest", "test_nothing_a_cell_reported_at_pr51_is_lost"):
    SINCE_PR47[2:],
    # the case holds its cell's entries to be the manifest's last
    ("test_backlog_minicpm_sala", "test_the_cell_lists_its_own_metrics"):
    SINCE_PR47[3:],
    ("test_backlog_granite4", "test_the_cell_lists_its_own_metrics"):
    SINCE_PR47[4:],
    ("test_train_mellum2", "test_the_cell_lists_its_own_metrics"):
    SINCE_PR47[5:]}
#: PR 54's four entries (the stall sentinel's two shares, twice), and the
#: cases that count what was there before them
PR54_ENTRIES = ("phase_overrun_share.rollout", "phase_overrun_share.train",
                "gc_pause_share.rollout", "gc_pause_share.train")
ENTRIES_SINCE = {
    ("test_backlog_phi4flash", "test_the_cell_lists_its_own_metrics"):
    PR54_ENTRIES,
    ("test_backlog_nemotron3", "test_the_cell_lists_its_own_metrics"):
    PR54_ENTRIES,
    ("test_manifest", "test_nothing_a_cell_reported_at_pr51_is_lost"):
    PR54_ENTRIES}
ON_PR47S_CELLS = ("test_the_list_has_room", "test_no_serve_cell_is_blind",
                  "test_nothing_a_cell_reported_at_pr46_is_lost")


def _load(name):
    """A module of `benchmark/tests/` by path. What it puts in FRONT of
    `sys.path` (the benchmark's directories: `common`, `workload`,
    `readers`, which it and its readers import by name) goes BEHIND
    what was there, so nothing of tier-1 is shadowed."""
    before = list(sys.path)
    spec = importlib.util.spec_from_file_location(
        "benchmark_tests_" + name, os.path.join(BENCH_TESTS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    sys.path[:] = before + [p for p in sys.path if p not in before]
    return module


def _without(manifest, cells, entries=()):
    """The manifest without `cells`, what only they report and the
    configurations only they run, and without the `entries` (metrics, by
    name)."""
    out = dict(manifest)
    out["workloads"] = [w for w in manifest["workloads"]
                        if w["name"] not in cells]
    # and without the configurations that only they run
    used = {w["config"] for w in out["workloads"]}
    out["configs"] = [c for c in manifest["configs"] if c["name"] in used]
    for key in ("end_to_end", "per_layer"):
        kept = []
        for entry in manifest[key]:
            if entry["name"] in entries:
                continue
            if "workloads" in entry:
                entry = dict(entry, workloads=[
                    c for c in entry["workloads"] if c not in cells])
                if not entry["workloads"]:
                    continue
            kept.append(entry)
        out[key] = kept
    return out


def _on_pr47s_cells(module, test):
    def run():
        whole = module._manifest
        module._manifest = lambda: _without(whole(), SINCE_PR47)
        try:
            test()
        finally:
            module._manifest = whole

    run.__doc__ = test.__doc__
    return run


def _on_the_manifest_without(module, test, cells, entries=()):
    """`test` with the module's loader giving the manifest without
    `cells` and `entries`; its fixtures are asked for under its own
    signature."""
    @functools.wraps(test)
    def run(*args, **kwargs):
        load = module.common.load_json

        def load_json(path):
            out = load(path)
            return _without(out, cells, entries) \
                if os.path.basename(path) == "BENCHMARK.json" else out

        module.common.load_json = load_json
        try:
            test(*args, **kwargs)
        finally:
            module.common.load_json = load

    return run


def _is_fixture(obj):
    return type(obj).__name__ == "FixtureFunctionDefinition" \
        or hasattr(obj, "_pytestfixturefunction")


for _name in MODULES:
    _module = _load(_name)
    for _attr, _obj in sorted(vars(_module).items()):
        if _is_fixture(_obj):
            assert _attr not in globals(), _attr    # one name, one fixture
            globals()[_attr] = _obj
        elif _attr.startswith("test_") and callable(_obj):
            if _attr in ON_PR47S_CELLS:
                _obj = _on_pr47s_cells(_module, _obj)
            elif (_name, _attr) in LAST_WHEN_WRITTEN \
                    or (_name, _attr) in ENTRIES_SINCE:
                _obj = _on_the_manifest_without(
                    _module, _obj,
                    LAST_WHEN_WRITTEN.get((_name, _attr), ()),
                    ENTRIES_SINCE.get((_name, _attr), ()))
            globals()["test_%s__%s" % (_name[len("test_"):],
                                       _attr[len("test_"):])] = _obj
