"""The backlog of `phi4flash_serve_rollout_reason_s64` and the cell's
entries in the manifest, on the CPU, with numpy and this directory's
generator and loader alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The file's multiset of sizes, its order's determinism from `order_seed`,
ids under the vocabulary, the parameters the issue gave letter for
letter, the headroom rule (PERF.md section 7 (8)) at the rate the cell
read on the chip, and the cell's own seven entries under `.phi4flash`
(the 22 common clocks it listed under that suffix until PR 52 are the
folded entries' now: `test_manifest.py` holds every serve cell to
them). Beside `test_backlogs.py`, `test_backlog_lfm2.py` and
`test_manifest.py`.
"""

import collections
import json
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
CELL = "phi4flash_serve_rollout_reason_s64"
SUFFIX = ".phi4flash"
PROMPTS = [128, 256, 256, 512, 512, 768, 1024, 1024]
OUTPUTS = [2048, 2487, 2926, 3365, 3803, 4242, 4681, 5120]
OWN = ("paged_diff_roofline", "paged_diff_window_roofline",
       "shared_kv_read_share", "window_read_share", "state_stream_share",
       "state_slot_share", "weight_stream_share")
# (my chip runs, PR 48; PERF.md section 5): serve_tokens_per_s, the median
# of six untraced runs; a pass without its admissions (`decode_s` over the
# window's steps), ms; an admission, ms
MEASURED = (2762.0, 22.34, 44.5)


@pytest.fixture(scope="module")
def phi_cell():
    return common.Cell(MANIFEST, CELL)


def _requests(cell, seed):
    requests = workload.request_groups(
        cell.traffic, seed, int(cell.traffic["requests"]),
        int(cell.config["vocab_size"]))
    workload.stagger_first(requests, int(cell.config["serving"]["slots"]))
    return requests


def test_the_file_carries_the_issues_parameters(phi_cell):
    tr, srv = phi_cell.traffic, phi_cell.config["serving"]
    assert tr["kind"] == "backlog_mapped_hybrid"
    assert workload.lengths_of(tr["prompt_lens"]) == PROMPTS
    assert workload.lengths_of(tr["output_lens"]) == OUTPUTS
    assert sum(PROMPTS) / len(PROMPTS) == 560
    assert sum(OUTPUTS) / len(OUTPUTS) == 3584
    assert (tr["requests"], tr["queue_depth"], tr["lead_in_steps"],
            tr["trace_seconds"]) == (256, 512, 512, 4)
    assert tr["prefill_buckets"] == [256, 512, 1024]
    assert (srv["slots"], srv["block_size"], srv["pool_blocks"],
            srv["max_new_tokens"], srv["max_context"]) \
        == (64, 16, 24577, 5120, 6144)
    assert srv["pool_blocks"] == srv["slots"] * srv["max_context"] \
        // srv["block_size"] + 1
    assert phi_cell.chips == 1 and phi_cell.entry["traffic"] \
        == "rollout_backlog_reason_s64"
    # the check admits at a length that is not its bucket's end, into a
    # slot a shorter sequence used before, and decodes 8 steps or more
    chk = tr["check"]
    assert chk["prompt_len"] not in tr["prefill_buckets"]
    assert max(tr["prefill_buckets"]) - 64 < chk["prompt_len"] \
        < max(tr["prefill_buckets"])
    assert 0 < chk["former_len"] < min(tr["prefill_buckets"])
    assert 0 < chk["slot"] < srv["slots"] and chk["decode_steps"] >= 8
    # past the window: the windows' blocks are released and taken
    assert chk["prompt_len"] > phi_cell.config["sliding_window"]


def test_the_configuration_keeps_the_catalogs_keys(phi_cell):
    cfg = phi_cell.config
    manifest = common.load_json(MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == phi_cell.entry["config"])
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    published = dict(
        embd_pdrop=0, hidden_act="silu", hidden_size=2560,
        intermediate_size=10240, layer_norm_eps=1e-05,
        max_position_embeddings=262144, mb_per_layer=2,
        model_type="phi4flash", num_attention_heads=40,
        num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
        tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False,
        vocab_size=200064)
    assert {k: cfg[k] for k in published} == published
    held = cfg["layers_held"]
    assert cfg["num_hidden_layers"] == len(held) == 16
    assert held == list(range(8)) + list(range(16, 24))
    assert cfg["published"]["num_hidden_layers"] == 32
    assert set(cfg["harness"]["limits"]) == {"row_max", "rms_max",
                                             "position_rms_max"}


def test_every_group_is_the_multiset(phi_cell):
    requests = workload.request_groups(
        phi_cell.traffic, 7, int(phi_cell.traffic["requests"]),
        int(phi_cell.config["vocab_size"]))
    assert len(requests) == 256
    for g in range(0, 256, 8):
        group = requests[g:g + 8]
        assert sorted(len(r["prompt"]) for r in group) == PROMPTS
        assert sorted(r["max_new"] for r in group) == OUTPUTS
        assert all(r["gap_s"] == 0 for r in group)
    pairs = collections.Counter((len(r["prompt"]), r["max_new"])
                                for r in requests[:64])
    assert pairs == collections.Counter(
        (p, o) for p in PROMPTS for o in OUTPUTS)
    srv = phi_cell.config["serving"]
    assert all(len(r["prompt"]) + r["max_new"] <= srv["max_context"]
               and r["max_new"] <= srv["max_new_tokens"] for r in requests)


@pytest.mark.parametrize("seed", [7, 2147487001])
def test_the_order_comes_from_order_seed_alone(phi_cell, seed):
    a, b = _requests(phi_cell, seed), _requests(phi_cell, seed + 1)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] \
        == [(len(r["prompt"]), r["max_new"]) for r in b]
    assert _requests(phi_cell, seed) == a             # and is repeatable
    assert a[0]["prompt"] != b[0]["prompt"]           # the ids are the seed's
    vocab = int(phi_cell.config["vocab_size"])
    assert all(0 <= t < vocab for r in a[:16] for t in r["prompt"])
    assert max(max(r["prompt"]) for r in a[:16]) > vocab // 2


def test_the_backlog_has_its_room(phi_cell):
    """Twice the measured rate of headroom, at the window's close and
    when the traced seconds end; and the slot model reads what the chip
    read."""
    traffic, slots, seconds = backlog_headroom.cell_files(MANIFEST, CELL)
    assert int(traffic["requests"]) < int(traffic["queue_depth"])
    rate, step_ms, admit_ms = MEASURED
    got = backlog_headroom.headroom(traffic, slots, step_ms, admit_ms,
                                    seconds)
    assert abs(got["tokens_per_s"] / rate - 1) < 0.03
    assert got["waiting_at_close"] > 0 and got["waiting_after_trace"] > 0
    assert got["dry_at_close_tokens_per_s"] >= 2 * rate
    assert got["dry_under_trace_tokens_per_s"] >= 2 * rate


def _file(name):
    return common.load_json(
        os.path.join(HERE, "layer_metrics", name + ".json"))


def test_the_cell_lists_its_own_metrics(phi_cell):
    """What is this architecture's own stays under its suffix, listing
    this cell alone; every common clock is the folded entry's, which
    names the cell (`test_manifest.py` holds that for every serve cell:
    the twins this file used to hold went with PR 52's fold)."""
    manifest = common.load_json(MANIFEST)
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    suffixed = [e for e in manifest["per_layer"]
                if e["name"].endswith(SUFFIX)]
    assert {e["name"] for e in suffixed} == {base + SUFFIX for base in OWN}
    assert len(suffixed) == 7
    assert all(e["workloads"] == [CELL] for e in suffixed)
    for base in OWN:
        entry, spec = by_name[base + SUFFIX], _file(base + SUFFIX)
        assert entry["moves"] == spec["moves"] == "serve_tokens_per_s"
        assert (entry["unit"], entry["layer"]) \
            == (spec["unit"], spec["layer"]) and entry["unit"] == "%"
    mine = [e["name"] for e in manifest["per_layer"]
            if CELL in e["workloads"]]
    assert len(mine) == 7 + 23 and "serve_step_mfu" in mine
    serve = next(e for e in manifest["end_to_end"]
                 if e["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
    assert set(phi_cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}


def test_the_streams_shares_add_up(phi_cell):
    """`readers/hybrid_stream.py` on made-up counters: the four shares
    are of ONE sum, the readers' part is three quarters of the shared
    pool's, and a parent that counts no pool readers reads nothing."""
    import flops_phi4flash
    from kinds import _model_phi4flash as mapping
    sz = mapping.sizes(phi_cell.config)
    model = dict(sz, **sz["block"])
    steps, rows = 100, 64 * 2400
    obs = dict(model=model, decode_steps=steps,
               pool_rows_read_writer=steps * rows,
               pool_rows_read_readers=3 * steps * rows,
               window_rows_read=4 * steps * 64 * 512,
               state_slot_steps=5 * steps * 64)
    ctx = dict(obs=obs, device=dict(platform="tpu", kind="TPU v5 lite"))
    shares = {w: common.read_metrics(
        {"m": dict(reader="hybrid_stream", params=dict(which=w),
                   unit="%")}, ctx)["m"]["value"]
        for w in ("shared", "window", "state")}
    parts = flops_phi4flash.decode_bytes(**{
        k: v for k, v in obs.items() if k != "model"}, **model)
    # the issue's arithmetic: 8.8 GB of weights, 6.3 GB of shared rows,
    # 1.3 GB of window rows, 0.25 GB of states, a step
    per_step = {k: v / steps / 1e9 for k, v in parts.items()}
    assert abs(per_step["weights"] - 8.77) < 0.02
    assert abs(per_step["shared"] - 6.29) < 0.01
    assert abs(per_step["window"] - 1.34) < 0.01
    assert abs(per_step["state"] - 0.249) < 0.001
    assert per_step["readers"] == 0.75 * per_step["shared"]
    least = sum(v for k, v in per_step.items() if k != "readers")
    for which, share in shares.items():
        assert abs(share - 100 * per_step[which] / least) < 1e-9
    assert 37 < shares["shared"] < 39
    # every live slot's state moved once a state layer a step
    obs["slots_capacity_sum"] = steps * 64
    assert common.read_metrics(
        {"m": dict(reader="hybrid_stream", params=dict(which="state_slots"),
                   unit="%")}, ctx)["m"]["value"] == 100.0
    del obs["pool_rows_read_readers"]
    assert common.read_metrics(
        {"m": dict(reader="hybrid_stream", params=dict(which="shared"),
                   unit="%")}, ctx) == {}
    # the kernels' costs: each live row once a call, 10,240 B
    _, nbytes = flops_phi4flash.paged_diff(
        context_tokens=rows, full_layers=1, reader_layers=3, calls=1,
        slots=64, heads=40, kv_heads=20, head_dim=64)
    assert nbytes == 4 * (rows * 10240 + 64 * 2 * 40 * 64 * 4)
    flops, nbytes = flops_phi4flash.paged_diff_window(
        window_rows=64 * 512, window_layers=4, calls=1, slots=64, heads=40,
        kv_heads=20, head_dim=64)
    assert nbytes == 4 * (64 * 512 * 10240 + 64 * 2 * 40 * 64 * 4)
    assert flops == 6.0 * 4 * 64 * 512 * 40 * 64
