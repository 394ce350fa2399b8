"""The backlog of `lfm2_24b_serve_rollout_6k_s64`, on the CPU, with numpy
and this directory's generator alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The file's multiset of sizes, its order's determinism from `order_seed`
(the run's seed draws the token ids and nothing else), ids under the
vocabulary, the parameters the issue gave letter for letter, and the
headroom rule (PERF.md section 7 (8)): at twice the rate the cell read on
the chip (PERF.md section 5, PR 43) requests still wait when the traced
seconds end. Beside `test_backlogs.py`, which is not edited.
"""

import collections
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
CELL = "lfm2_24b_serve_rollout_6k_s64"
PROMPTS = [2048, 2048, 3072, 4096, 4096, 6144, 6144, 6144]
OUTPUTS = [1024, 1463, 1902, 2341, 2779, 3218, 3657, 4096]
# (my chip runs, PR 43; PERF.md section 5): serve_tokens_per_s, the median
# of six untraced runs; a pass without its admissions (`decode_s` over the
# window's steps, the traced run), ms; an admission, ms
MEASURED = (3406.4, 16.81, 74.1)


@pytest.fixture(scope="module")
def cell():
    return common.Cell(MANIFEST, CELL)


def _requests(cell, seed):
    requests = workload.request_groups(
        cell.traffic, seed, int(cell.traffic["requests"]),
        int(cell.config["vocab_size"]))
    workload.stagger_first(requests, int(cell.config["serving"]["slots"]))
    return requests


def test_the_file_carries_the_issues_parameters(cell):
    tr, srv = cell.traffic, cell.config["serving"]
    assert tr["kind"] == "backlog_mapped_state"
    assert workload.lengths_of(tr["prompt_lens"]) == PROMPTS
    assert workload.lengths_of(tr["output_lens"]) == OUTPUTS
    assert sum(PROMPTS) / len(PROMPTS) == 4224
    assert (tr["requests"], tr["queue_depth"], tr["lead_in_steps"],
            tr["trace_seconds"]) == (256, 512, 256, 4)
    assert tr["prefill_buckets"] == [2048, 4096, 6144]
    assert (srv["slots"], srv["block_size"], srv["pool_blocks"],
            srv["max_new_tokens"], srv["max_context"]) \
        == (64, 16, 40961, 4096, 10240)
    assert srv["pool_blocks"] == srv["slots"] * srv["max_context"] \
        // srv["block_size"] + 1
    assert cell.chips == 1 and cell.entry["traffic"] \
        == "rollout_backlog_6k_s64"
    # the check admits at a length that is not its bucket's end, into a
    # slot a shorter sequence used before
    chk = tr["check"]
    assert chk["prompt_len"] not in tr["prefill_buckets"]
    assert max(tr["prefill_buckets"]) - 64 < chk["prompt_len"] \
        < max(tr["prefill_buckets"])
    assert 0 < chk["former_len"] < chk["prompt_len"]
    assert 0 < chk["slot"] < srv["slots"] and chk["decode_steps"] >= 4


def test_every_group_is_the_multiset(cell):
    requests = workload.request_groups(
        cell.traffic, 7, int(cell.traffic["requests"]),
        int(cell.config["vocab_size"]))
    assert len(requests) == 256
    for g in range(0, 256, 8):
        group = requests[g:g + 8]
        assert sorted(len(r["prompt"]) for r in group) == PROMPTS
        assert sorted(r["max_new"] for r in group) == OUTPUTS
        assert all(r["gap_s"] == 0 for r in group)
    # eight consecutive groups hold every pairing once
    pairs = collections.Counter((len(r["prompt"]), r["max_new"])
                                for r in requests[:64])
    assert pairs == collections.Counter(
        (p, o) for p in PROMPTS for o in OUTPUTS)
    # every request fits its context and its bucket
    srv = cell.config["serving"]
    assert all(len(r["prompt"]) + r["max_new"] <= srv["max_context"]
               and r["max_new"] <= srv["max_new_tokens"] for r in requests)


@pytest.mark.parametrize("seed", [7, 2147487001])
def test_the_order_comes_from_order_seed_alone(cell, seed):
    a, b = _requests(cell, seed), _requests(cell, seed + 1)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] \
        == [(len(r["prompt"]), r["max_new"]) for r in b]
    assert _requests(cell, seed) == a                 # and is repeatable
    assert a[0]["prompt"] != b[0]["prompt"]           # the ids are the seed's
    vocab = int(cell.config["vocab_size"])
    assert all(0 <= t < vocab for r in a[:16] for t in r["prompt"])
    assert max(max(r["prompt"]) for r in a[:16]) > vocab // 2
    # another order_seed, another order of the same sizes
    other = dict(cell.traffic, order_seed=int(cell.traffic["order_seed"]) + 1)
    c = workload.request_groups(other, seed, 256, vocab)
    plain = workload.request_groups(cell.traffic, seed, 256, vocab)
    assert [len(r["prompt"]) for r in c] != [len(r["prompt"]) for r in plain]
    assert sorted(len(r["prompt"]) for r in c) \
        == sorted(len(r["prompt"]) for r in plain)


def test_the_first_slots_are_out_of_phase(cell):
    requests = _requests(cell, 3)
    first = [r["max_new"] for r in requests[:64]]
    assert min(first) >= 2 and len(set(first)) > 48
    # 655 k output tokens as written, 574 k with the first 64 cut short
    assert sum(r["max_new"] for r in requests) > 550_000


def test_the_backlog_has_its_room(cell):
    """Twice the measured rate of headroom, at the window's close and
    when the traced seconds end; and the slot model reads what the chip
    read."""
    traffic, slots, seconds = backlog_headroom.cell_files(MANIFEST, CELL)
    assert int(traffic["requests"]) < int(traffic["queue_depth"])
    rate, step_ms, admit_ms = MEASURED
    got = backlog_headroom.headroom(traffic, slots, step_ms, admit_ms,
                                    seconds)
    # the model reads what the chip read, so its dry points mean it
    assert abs(got["tokens_per_s"] / rate - 1) < 0.03
    assert got["waiting_at_close"] > 0 and got["waiting_after_trace"] > 0
    assert got["dry_at_close_tokens_per_s"] >= 2 * rate
    assert got["dry_under_trace_tokens_per_s"] >= 2 * rate
