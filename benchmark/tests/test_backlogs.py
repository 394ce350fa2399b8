"""What a longer backlog may and may not change, on the CPU, with numpy
and this directory's generator alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

(a) the first 256 requests of a 512-request file are its parent's 256,
prompt for prompt and `max_new` for `max_new`, after `stagger_first`;
(b) apart from `requests` and `queue_depth` the file is its parent key for
key; (c) every backlog cell of `BENCHMARK.json` names a traffic file that
is there, holds fewer requests than the queue takes, and by
`tools/backlog_headroom.py` still has requests waiting at the end of the
traced seconds at twice the rate the ledger has of it (the two
short-prompt cells also at a pass of 6 ms: 2,300-2,400 tokens/s).
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "tools")]

import backlog_headroom  # noqa: E402
import common  # noqa: E402
import workload  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
# (parent, its longer twin, the configuration whose vocabulary draws the ids)
PAIRS = [("rollout_backlog", "rollout_backlog_512",
          "cerebras-gpt-1.3b-serve"),
         ("rollout_backlog_1k", "rollout_backlog_1k_512",
          "olmoe-1b-7b-0125-serve")]
# (ledger, PR 40, change side): serve_tokens_per_s; a pass without its
# admissions = step_dispatch + step_wait + step_fetch + step_sched +
# starved_loop, ms; an admission = prefill_device + seed_kv + prefill_fetch
LEDGER = {"cgpt1p3b_serve_rollout": (1198.0, 12.58, 12.55),
          "olmoe1b7b_serve_rollout": (1077.3, 13.96, 16.96),
          "kanana2_30b_serve_rollout_6k": (1157.6, 13.04, 101.7),
          "keye2_30b_serve_rollout_6k": (716.85, 19.38, 193.4),
          "cmdaplus_serve_rollout_10k": (590.23, 19.57, 200.1)}
# the two short-prompt cells besides: a pass of 6 ms (2,413 and 2,336
# tokens/s with their admissions) leaves requests waiting through the
# traced seconds
SIX_MS = ("cgpt1p3b_serve_rollout", "olmoe1b7b_serve_rollout")


def _json(*parts):
    return common.load_json(os.path.join(*parts))


def _traffic(name):
    return _json(HERE, "traffic", name + ".json")


def _requests(traffic, config, seed):
    cfg = _json(HERE, "configs", config + ".json")
    slots = int(cfg["serving"]["slots"])
    requests = workload.request_groups(
        traffic, seed, int(traffic["requests"]), int(cfg["vocab_size"]))
    workload.stagger_first(requests, slots)
    return requests


def _sizes(requests):
    return sorted((len(r["prompt"]), r["max_new"]) for r in requests)


def _backlog_cells():
    manifest = _json(MANIFEST)
    out = []
    for cell in manifest["workloads"]:
        path = os.path.join(HERE, "traffic", cell["traffic"] + ".json")
        if not os.path.exists(path):
            out.append(cell["name"])    # the test says which file is missing
            continue
        if str(_json(path).get("kind", "")).startswith("backlog"):
            out.append(cell["name"])
    return out


@pytest.mark.parametrize("seed", [7, 2147487001])
@pytest.mark.parametrize("parent,twin,config", PAIRS)
def test_first_requests_are_the_parents(parent, twin, config, seed):
    short = _requests(_traffic(parent), config, seed)
    long = _requests(_traffic(twin), config, seed)
    assert len(short) == 256 and len(long) == 512
    assert long[:len(short)] == short
    # and what follows is more of the same mix: eight groups hold every
    # pairing of a prompt length with an output length once
    assert _sizes(long[256:320]) == _sizes(long[320:384])


@pytest.mark.parametrize("parent,twin,config", PAIRS)
def test_twin_differs_in_its_length_alone(parent, twin, config):
    a, b = _traffic(parent), _traffic(twin)
    assert list(a) == list(b)
    differ = {k for k in a if a[k] != b[k]}
    assert differ == {"requests", "queue_depth"}
    assert (b["requests"], b["queue_depth"]) == (512, 1024)


@pytest.mark.parametrize("name", _backlog_cells())
def test_backlog_cell_has_its_file_and_its_room(name):
    traffic, slots, seconds = backlog_headroom.cell_files(MANIFEST, name)
    assert int(traffic["requests"]) < int(traffic["queue_depth"])
    if name in LEDGER:
        rate, step_ms, admit_ms = LEDGER[name]
        got = backlog_headroom.headroom(traffic, slots, step_ms, admit_ms,
                                        seconds)
        # the model reads what the chip read, so its dry points mean it
        assert abs(got["tokens_per_s"] / rate - 1) < 0.03
        assert got["dry_at_close_tokens_per_s"] >= 2 * rate
        assert got["dry_under_trace_tokens_per_s"] >= 2 * rate
    if name in SIX_MS:
        got = backlog_headroom.headroom(traffic, slots, 6.0,
                                        LEDGER[name][2], seconds)
        assert got["tokens_per_s"] >= 2 * LEDGER[name][0]
        assert got["waiting_at_close"] > 0
        assert got["waiting_after_trace"] > 0


@pytest.mark.parametrize("parent,twin,config", PAIRS)
def test_dry_points_are_twice_the_ledgers(parent, twin, config):
    """The two cells this PR lengthened read 1,198 and 1,077 tokens/s
    (ledger, PR 40): 2,400 is twice the larger."""
    traffic = _traffic(twin)
    got = backlog_headroom.headroom(traffic, 16, 11.9, 12.4, 51)
    assert got["dry_at_close_tokens_per_s"] >= 2400
    assert got["dry_under_trace_tokens_per_s"] >= 2400
    old = backlog_headroom.headroom(_traffic(parent), 16, 11.9, 12.4, 51)
    assert old["dry_under_trace_tokens_per_s"] < 1400
