"""What the MiniCPM-SALA cell's files must keep true, on the CPU, with this
directory's loader alone (no program; JAX for nothing):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The file's multiset of sizes, its order's determinism from `order_seed`,
ids under the vocabulary, the parameters the issue gave letter for
letter, the catalog's keys, the cut's arithmetic, the headroom rule
(PERF.md section 7 (8)) at the rate the cell read on the chip, the
cell's own five entries under `.sala` (the common clocks are the folded
entries', which name the cell: `test_manifest.py` holds every serve cell
to them), the arithmetic behind the three stream shares, and the
kernels' costs. Beside `test_backlogs.py`, `test_backlog_lfm2.py`,
`test_backlog_phi4flash.py`, `test_backlog_nemotron3.py` and
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
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "minicpm_sala_serve_rollout_32k"
SUFFIX = ".sala"
PROMPTS = [12288, 12288, 16384, 16384, 16384, 24576, 24576, 32768]
OUTPUTS = [2048, 2341, 2633, 2926, 3218, 3511, 3803, 4096]
OWN = ("paged_block_sparse_roofline", "lightning_update_roofline",
       "kv_stream_share", "state_stream_share", "weight_stream_share")
# (my chip runs, PR 56; PERF.md section 5): serve_tokens_per_s, the median
# of the untraced runs; a pass without its admissions (`decode_s` over the
# window's steps), ms; an admission, ms
MEASURED = (3070.7, 11.1, 469.2)


@pytest.fixture(scope="module")
def sala_cell():
    return common.Cell(MANIFEST, CELL)


def _requests(cell, seed):
    requests = workload.request_groups(
        cell.traffic, seed, int(cell.traffic["requests"]),
        int(cell.config["vocab_size"]))
    workload.stagger_first(requests, int(cell.config["serving"]["slots"]))
    return requests


def test_the_file_carries_the_issues_parameters(sala_cell):
    tr, srv = sala_cell.traffic, sala_cell.config["serving"]
    assert tr["kind"] == "backlog_mapped_blk"
    assert workload.lengths_of(tr["prompt_lens"]) == PROMPTS
    assert workload.lengths_of(tr["output_lens"]) == OUTPUTS
    assert sum(PROMPTS) / len(PROMPTS) == 19456
    assert sum(OUTPUTS) / len(OUTPUTS) == 3072
    assert (tr["requests"], tr["queue_depth"], tr["lead_in_steps"],
            tr["trace_seconds"]) == (192, 256, 512, 4)
    assert tr["prefill_buckets"] == [12288, 16384, 24576, 32768]
    assert (srv["slots"], srv["block_size"], srv["pool_blocks"],
            srv["max_new_tokens"], srv["max_context"]) \
        == (64, 64, 36865, 4096, 36864)
    # every slot at `max_context` has its blocks: no preemption
    assert srv["pool_blocks"] == srv["slots"] * srv["max_context"] \
        // srv["block_size"] + 1
    assert sala_cell.chips == 1 and sala_cell.entry["traffic"] \
        == "rollout_backlog_32k_blk"
    sparse = sala_cell.config["assumed"]["sparse_config"]
    # a page is the selection's block; every prompt is over `dense_len`
    assert srv["block_size"] == sparse["block_size"] == 64
    assert min(PROMPTS) > sparse["dense_len"] == 8192
    # the check admits at a length that is not its bucket's end, into a
    # slot a shorter (dense) sequence used before, and decodes 8 steps,
    # each of which chooses 64 of 188 blocks
    chk = tr["check"]
    assert chk == {"prompt_len": 12000, "decode_steps": 8,
                   "former_len": 2000, "slot": 5}
    assert chk["prompt_len"] not in tr["prefill_buckets"]
    assert chk["former_len"] < sparse["dense_len"] < chk["prompt_len"]
    assert -(-(chk["prompt_len"] + chk["decode_steps"])
             // sparse["block_size"]) == 188 > sparse["topk"] == 64


def test_the_configuration_keeps_the_catalogs_keys(sala_cell):
    cfg = sala_cell.config
    manifest = common.load_json(MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == sala_cell.entry["config"])
    reduced = ["num_hidden_layers"]
    assert entry["reduced"] == cfg["reduced"] == reduced
    assert entry["source"] == cfg["source"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["source_url"] == cfg["source"])
        for key, value in row["config"].items():
            if key not in reduced:
                assert cfg[key] == value, key
    # the widths, whatever the catalog file says tomorrow
    assert {k: cfg[k] for k in (
        "hidden_size", "head_dim", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "lightning_nh",
        "lightning_nkv", "lightning_head_dim", "vocab_size", "scale_emb",
        "scale_depth", "dim_model_base")} == dict(
        hidden_size=4096, head_dim=128, num_attention_heads=32,
        num_key_value_heads=2, intermediate_size=16384, lightning_nh=32,
        lightning_nkv=32, lightning_head_dim=128, vocab_size=73448,
        scale_emb=12, scale_depth=1.4, dim_model_base=256)
    assert len(cfg["mixer_types"]) == 32
    assert cfg["mixer_types"][:4] == ["minicpm4"] + ["lightning-attn"] * 3
    assert [i for i, m in enumerate(cfg["mixer_types"])
            if m == "minicpm4"] == [0, 9, 16, 17, 22, 29, 30, 31]
    assert cfg["num_hidden_layers"] == 4
    assert cfg["published"]["num_hidden_layers"] == 32
    assert set(cfg["reduced_how"]) == set(reduced) | {"sum"}
    assert {"stands_for", "assumed"} <= set(cfg)
    assert cfg["assumed"]["sparse_config"] == dict(
        kernel_size=32, kernel_stride=16, block_size=64, topk=64,
        window_size=2048, init_blocks=1, dense_len=8192)
    assert {"linear_decay", "dense_rule", "normaliser", "qk_gain",
            "mup_denominator", "dtype", "weights"} <= set(cfg["assumed"])
    assert set(cfg["harness"]["limits"]) == {"row_max", "rms_max",
                                             "tie_max"}
    assert cfg["harness"]["flops"] == "flops_minicpm_sala"


def test_every_group_is_the_multiset(sala_cell):
    requests = workload.request_groups(
        sala_cell.traffic, 7, int(sala_cell.traffic["requests"]),
        int(sala_cell.config["vocab_size"]))
    assert len(requests) == 192
    for g in range(0, 192, 8):
        group = requests[g:g + 8]
        assert sorted(len(r["prompt"]) for r in group) == PROMPTS
        assert sorted(r["max_new"] for r in group) == OUTPUTS
        assert all(r["gap_s"] == 0 for r in group)
    pairs = collections.Counter((len(r["prompt"]), r["max_new"])
                                for r in requests[:64])
    assert pairs == collections.Counter(
        (p, o) for p in PROMPTS for o in OUTPUTS)
    srv = sala_cell.config["serving"]
    assert all(len(r["prompt"]) + r["max_new"] <= srv["max_context"]
               and r["max_new"] <= srv["max_new_tokens"] for r in requests)


@pytest.mark.parametrize("seed", [7, 2147487001])
def test_the_order_comes_from_order_seed_alone(sala_cell, seed):
    a, b = _requests(sala_cell, seed), _requests(sala_cell, seed + 1)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] \
        == [(len(r["prompt"]), r["max_new"]) for r in b]
    assert _requests(sala_cell, seed) == a            # and is repeatable
    assert a[0]["prompt"] != b[0]["prompt"]           # the ids are the seed's
    vocab = int(sala_cell.config["vocab_size"])
    assert all(0 <= t < vocab for r in a[:16] for t in r["prompt"])
    assert max(max(r["prompt"]) for r in a[:16]) > vocab // 2


def test_the_backlog_has_its_room(sala_cell):
    """Twice the measured rate of headroom, at the window's close and
    when the traced seconds end; and the slot model reads what the chip
    read."""
    traffic, slots, seconds = backlog_headroom.cell_files(MANIFEST, CELL)
    rate, step_ms, admit_ms = MEASURED
    got = backlog_headroom.headroom(traffic, slots, step_ms, admit_ms,
                                    seconds)
    assert abs(got["tokens_per_s"] / rate - 1) < 0.05
    assert got["waiting_at_close"] > 0 and got["waiting_after_trace"] > 0
    # None: no rate the model can reach drains it by then
    for dry in ("dry_at_close_tokens_per_s", "dry_under_trace_tokens_per_s"):
        assert got[dry] is None or got[dry] >= 2 * rate, dry


def _file(name):
    return common.load_json(
        os.path.join(HERE, "layer_metrics", name + ".json"))


def test_the_cell_lists_its_own_metrics(sala_cell):
    """What is this architecture's own stays under its suffix, listing
    this cell alone and LAST in the manifest (appended: nothing put in
    the middle); every common clock is the folded entry's, which names
    the cell last; the selection's share is Keye's entry, no twin."""
    manifest = common.load_json(MANIFEST)
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    suffixed = [e for e in manifest["per_layer"]
                if e["name"].endswith(SUFFIX)]
    assert [e["name"] for e in suffixed] == [b + SUFFIX for b in OWN]
    assert manifest["per_layer"][-len(OWN):] == suffixed
    assert all(e["workloads"] == [CELL] for e in suffixed)
    for base in OWN:
        entry, spec = by_name[base + SUFFIX], _file(base + SUFFIX)
        assert entry["moves"] == spec["moves"] == "serve_tokens_per_s"
        assert (entry["unit"], entry["layer"]) \
            == (spec["unit"], spec["layer"]) and entry["unit"] == "%"
    for base in OWN[:2]:
        assert by_name[base + SUFFIX]["source"] == "device_trace"
        assert by_name[base + SUFFIX]["layer"] == "kernels"
        assert _file(base + SUFFIX)["reader"] == "kernel_roofline_of"
    assert _file(OWN[1] + SUFFIX)["params"]["match"] == ["ssd_decode_update"]
    shared = [e for e in manifest["per_layer"]
              if CELL in e["workloads"] and not e["name"].endswith(SUFFIX)]
    assert len(shared) == 26 and all(e["workloads"][-1] == CELL
                                     for e in shared)
    assert "sparse_select_share.keye" in [e["name"] for e in shared]
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == sala_cell.entry["config"]
    serve = next(e for e in manifest["end_to_end"]
                 if e["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == CELL
    assert set(sala_cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}


def _model(cell):
    from kinds import _model_minicpm_sala as mapping
    sz = mapping.sizes(cell.config)
    return dict(sz, **sz["block"])


def test_the_cut_is_the_issues_arithmetic(sala_cell):
    """The parameters and bytes of the configuration's `reduced_how`,
    from the sizes the mapping hands the program."""
    m = _model(sala_cell)
    d, wide, narrow, f = m["d_model"], 32 * 128, 2 * 128, m["d_ff"]
    assert (d, wide, narrow, f) == (4096, 4096, 256, 16384)
    linear = 5 * d * wide + 3 * d * f
    sparse = 3 * d * wide + 2 * d * narrow + 3 * d * f
    vocabulary = 2 * m["vocab"] * d
    assert round(linear / 1e6, 1) == 285.2
    assert round(sparse / 1e6, 1) == 253.8
    assert round(vocabulary / 1e6, 1) == 601.7
    whole = 24 * linear + 8 * sparse + vocabulary
    assert round(whole / 1e9, 2) == 9.48 and round(4 * whole / 1e9, 1) == 37.9
    held = 3 * linear + sparse + vocabulary
    assert round(held / 1e6) == 1711 and round(4 * held / 1e9, 2) == 6.84
    srv = sala_cell.config["serving"]
    state = 4 * 32 * 128 * 128
    assert state == 2097152
    states = srv["slots"] * 3 * state
    kv = srv["pool_blocks"] * srv["block_size"] * 2 * narrow * 4
    pooled = srv["slots"] * ((srv["max_context"] - 32) // 16 + 1) \
        * narrow * 4
    assert (round(states / 1e9, 2), round(kv / 1e9, 2),
            round(pooled / 1e9, 2)) == (0.40, 4.83, 0.15)
    total = 4 * held + states + kv + pooled
    assert round(total / 1e9, 1) == 12.2 and total > 0.6 * 16 * 2 ** 30
    assert (m["state_layers"], m["full_layers"], m["n_layers"]) == (3, 1, 4)
    assert abs(m["residual_scale"] - 1.4 / 32 ** 0.5) < 1e-12
    assert (m["embed_scale"], m["logit_scale"]) == (12.0, 1 / 16)
    assert m["layer_pattern"] == ["blocksparse"] + ["linear"] * 3
    assert m["decay_layers"] == 32


def _obs(model, steps=100, slots=64, rows=21000):
    """A window's counters at the issue's contexts: every slot live at
    21 k rows, choosing 64 blocks (63 full and its own, half full)."""
    chosen = 63 * 64 + 32
    return dict(model=model, decode_steps=steps, block_size=64,
                state_slot_steps=3 * steps * slots,
                sparse_live_rows=steps * slots * rows,
                sparse_selected_rows=steps * slots * chosen,
                block_pooled_rows=steps * slots * ((rows - 32) // 16 + 1),
                paged_live_pages=steps * slots * -(-rows // 64),
                slots_used_sum=steps * slots)


def test_the_streams_shares_add_up(sala_cell):
    """`readers/minicpm_sala_stream.py` on made-up counters at the
    issue's contexts: the shares are of ONE sum, 7.07 GB a step: 4.43 GB
    of layers and 1.20 of head, 0.62 of pooled keys and chosen blocks,
    0.81 of states; a parent that counts no chosen rows reads nothing."""
    import flops_minicpm_sala
    model = _model(sala_cell)
    obs = _obs(model)
    ctx = dict(obs=obs, device=dict(platform="tpu", kind="TPU v5 lite"))
    shares = {w: common.read_metrics(
        {"m": dict(reader="minicpm_sala_stream", params=dict(which=w),
                   unit="%")}, ctx)["m"]["value"]
        for w in ("state", "kv")}
    parts = flops_minicpm_sala.decode_bytes(obs, **model)
    per_step = {k: v / 100 / 1e9 for k, v in parts.items()}
    assert abs(per_step["state"] - 64 * 3 * 2 * 2097152 / 1e9) < 1e-9
    assert abs(per_step["state"] - 0.81) < 0.01
    assert abs(per_step["kv"] - 64 * (2 * 4064 + 1311) * 1024 / 1e9) < 1e-9
    assert abs(per_step["kv"] - 0.62) < 0.01
    assert abs(per_step["weights"] - (4.43 + 1.20)) < 0.02
    least = sum(per_step.values())
    assert abs(least - 7.07) < 0.03
    for which, share in shares.items():
        assert abs(share - 100 * per_step[which] / least) < 1e-9
    assert 11 < shares["state"] < 12 and 8 < shares["kv"] < 9.5
    # dense attention over the same slots would read 2.75 GB of K/V
    assert abs(64 * 21000 * 2048 / 1e9 - 2.75) < 0.01
    whole = flops_minicpm_sala.decode_least_bytes(obs, **model)
    assert sum(whole.values()) == sum(parts.values())
    a_pass = flops_minicpm_sala.pass_weight_bytes(**model)
    assert abs(a_pass["always"] / 1e9 - 5.63) < 0.02
    assert abs(a_pass["head"] / 1e9 - 1.20) < 0.01
    assert (a_pass["expert"], a_pass["routed"]) == (0.0, 0)
    del obs["sparse_selected_rows"]
    assert common.read_metrics(
        {"m": dict(reader="minicpm_sala_stream", params=dict(which="state"),
                   unit="%")}, ctx) == {}


def test_the_kernels_costs():
    """The state update: 2 x 2,097,152 B a live slot and layer; the
    block-sparse call: 1,024 B a chosen row and K/V head (K and V of one
    head of 128), so 8.4 MB a slot that reads 64 blocks."""
    import flops_minicpm_sala
    flops, nbytes = flops_minicpm_sala.lightning_update(
        live_slot_steps=64 * 3, heads=32, head_dim=128)
    assert nbytes == 64 * 3 * 2 * 2097152
    assert flops / 197e12 < nbytes / 819e9          # the bytes bound it
    flops, nbytes = flops_minicpm_sala.paged_block_sparse(
        selected_rows=4096, full_layers=1, calls=1, slots=1, heads=32,
        kv_heads=2, head_dim=128)
    assert nbytes == 4096 * 2 * 1024 + 2 * 32 * 128 * 4
    assert abs(nbytes / 1e6 - 8.4) < 0.05
    assert flops / 197e12 < nbytes / 819e9
