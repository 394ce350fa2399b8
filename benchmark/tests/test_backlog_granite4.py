"""The backlog of `granite4_h_micro_serve_rollout_reason_s48` and the
cell's entries in the manifest, on the CPU, with numpy and this
directory's generator and loader alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The file's multiset of sizes, its order's determinism from `order_seed`,
ids under the vocabulary, the parameters the issue gave letter for
letter, the catalog's keys (ALL of them: `reduced` is empty), the
headroom rule (PERF.md section 7 (8)) at the rate the cell read on the
chip, the cell's own six entries under `.granite4` beside the folded
common clocks that name it last, the arithmetic of the configuration's
file from the sizes the mapping hands the program, and the arithmetic
behind `state_stream_share.granite4`. Beside `test_backlogs.py`, the
other cells' files and `test_manifest.py`.
"""

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
CELL = "granite4_h_micro_serve_rollout_reason_s48"
SUFFIX = ".granite4"
PROMPTS = [128, 256, 256, 512, 512, 768, 1024, 1024]
OUTPUTS = [2048, 2344, 2632, 2928, 3216, 3512, 3800, 4096]
OWN = ("ssd_update_roofline", "paged_decode_roofline", "state_stream_share",
       "kv_stream_share", "weight_stream_share", "state_slot_share")
# (my chip run, PR 58; PERF.md section 5: the cell's first run, traced,
# seed 2147483659): tokens emitted over the window, 103,344 / 51.0 s; a
# pass without its admissions (`decode_s` over the window's steps,
# 49.096 s / 2,153), ms; an admission (`prefill_s` 1.902 s / 32), ms
MEASURED = (2026.4, 22.80, 59.45)


@pytest.fixture(scope="module")
def granite_cell():
    return common.Cell(MANIFEST, CELL)


def _requests(cell, seed):
    requests = workload.request_groups(
        cell.traffic, seed, int(cell.traffic["requests"]),
        int(cell.config["vocab_size"]))
    workload.stagger_first(requests, int(cell.config["serving"]["slots"]))
    return requests


def test_the_file_carries_the_issues_parameters(granite_cell):
    tr, srv = granite_cell.traffic, granite_cell.config["serving"]
    assert tr["kind"] == "backlog_mapped_dense_ssd"
    assert workload.lengths_of(tr["prompt_lens"]) == PROMPTS
    assert workload.lengths_of(tr["output_lens"]) == OUTPUTS
    assert all(n % 8 == 0 for n in OUTPUTS)
    assert sum(PROMPTS) / len(PROMPTS) == 560
    assert sum(OUTPUTS) / len(OUTPUTS) == 3072
    assert (tr["requests"], tr["queue_depth"], tr["lead_in_steps"],
            tr["trace_seconds"]) == (256, 512, 512, 4)
    assert tr["prefill_buckets"] == [512, 1024]
    assert (srv["weight_dtype"], srv["dtype"], srv["slots"],
            srv["block_size"], srv["pool_blocks"], srv["max_new_tokens"],
            srv["max_context"]) == ("bfloat16", "float32", 48, 16, 15361,
                                    4096, 5120)
    assert srv["pool_blocks"] == srv["slots"] * srv["max_context"] \
        // srv["block_size"] + 1
    assert max(PROMPTS) + max(OUTPUTS) == srv["max_context"]
    assert granite_cell.chips == 1 and granite_cell.entry["traffic"] \
        == "rollout_backlog_reason_s48"
    # the check admits at a length that is not its bucket's end nor whole
    # chunks, into a slot a shorter sequence used before, and decodes 8
    chk = tr["check"]
    assert chk == {"prompt_len": 1000, "decode_steps": 8,
                   "former_len": 200, "slot": 5}
    assert chk["prompt_len"] not in tr["prefill_buckets"]
    assert chk["prompt_len"] % granite_cell.config["mamba_chunk_size"]


def test_the_configuration_keeps_every_catalog_key(granite_cell):
    cfg = granite_cell.config
    manifest = common.load_json(MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == granite_cell.entry["config"])
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/granite-4.0-h-micro-serve.json"
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["source_url"] == cfg["source"])
        assert row["name"] == "granite-4.0-h-micro"
        for key, value in row["config"].items():
            assert cfg[key] == value, key
    # the widths and counts, whatever the catalog file says tomorrow
    assert {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "shared_intermediate_size", "mamba_n_heads", "mamba_d_head",
        "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
        "mamba_chunk_size", "num_hidden_layers", "vocab_size",
        "num_local_experts", "attention_multiplier", "residual_multiplier",
        "embedding_multiplier", "logits_scaling")} == dict(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=8,
        shared_intermediate_size=8192, mamba_n_heads=64, mamba_d_head=64,
        mamba_n_groups=1, mamba_d_state=128, mamba_d_conv=4,
        mamba_chunk_size=256, num_hidden_layers=40, vocab_size=100352,
        num_local_experts=0, attention_multiplier=0.015625,
        residual_multiplier=0.22, embedding_multiplier=12,
        logits_scaling=8)
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] \
        == [5, 15, 25, 35]
    assert {"stands_for", "assumed", "published", "arithmetic"} <= set(cfg)
    assert set(cfg["harness"]["limits"]) == {"row_max", "rms_max"}
    assert set(cfg["harness"]["limits_why"]) >= {"readings", "row_max",
                                                 "rms_max"}
    assert (cfg["harness"]["mapping"], cfg["harness"]["reference"],
            cfg["harness"]["flops"]) == (
        "_model_granite4", "reference_granite4", "flops_granite4")


def test_every_group_is_the_multiset(granite_cell):
    requests = workload.request_groups(
        granite_cell.traffic, 7, int(granite_cell.traffic["requests"]),
        int(granite_cell.config["vocab_size"]))
    assert len(requests) == 256
    for g in range(0, 256, 8):
        group = requests[g:g + 8]
        assert sorted(len(r["prompt"]) for r in group) == PROMPTS
        assert sorted(r["max_new"] for r in group) == OUTPUTS
        assert all(r["gap_s"] == 0 for r in group)
    srv = granite_cell.config["serving"]
    assert all(len(r["prompt"]) + r["max_new"] <= srv["max_context"]
               and r["max_new"] <= srv["max_new_tokens"] for r in requests)


@pytest.mark.parametrize("seed", [7, 2147487001])
def test_the_order_comes_from_order_seed_alone(granite_cell, seed):
    a, b = _requests(granite_cell, seed), _requests(granite_cell, seed + 1)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] \
        == [(len(r["prompt"]), r["max_new"]) for r in b]
    assert _requests(granite_cell, seed) == a         # and is repeatable
    assert a[0]["prompt"] != b[0]["prompt"]           # the ids are the seed's
    vocab = int(granite_cell.config["vocab_size"])
    assert all(0 <= t < vocab for r in a[:16] for t in r["prompt"])
    assert max(max(r["prompt"]) for r in a[:16]) > vocab // 2


def test_the_backlog_has_its_room(granite_cell):
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


def test_the_cell_lists_its_own_metrics(granite_cell):
    """What is this architecture's own stays under its suffix, listing
    this cell alone and LAST in the manifest (appended: nothing put in
    the middle); every common clock is the folded entry's, which names
    the cell last; no twin of an entry that was there."""
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
        spec = _file(base + SUFFIX)
        assert (spec["reader"], spec["params"]["module"]) \
            == ("kernel_roofline_of", "flops_granite4")
    assert _file(OWN[0] + SUFFIX)["params"]["match"] == ["ssd_decode_update"]
    assert _file(OWN[1] + SUFFIX)["params"]["match"] == ["paged_attention"]
    for base in OWN[2:]:
        assert _file(base + SUFFIX)["reader"] == "granite4_stream"
    shared = [e for e in manifest["per_layer"]
              if CELL in e["workloads"] and not e["name"].endswith(SUFFIX)]
    assert len(shared) == 25 and all(e["workloads"][-1] == CELL
                                     for e in shared)
    assert "serve_step_mfu" in [e["name"] for e in shared]
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == granite_cell.entry["config"]
    serve = next(e for e in manifest["end_to_end"]
                 if e["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == CELL
    assert set(granite_cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}


def _model(cell):
    from kinds import _model_granite4 as mapping
    sz = mapping.sizes(cell.config)
    return dict(sz, **sz["block"])


def test_the_file_holds_the_issues_arithmetic(granite_cell):
    """The parameters and bytes of the configuration's `arithmetic`, from
    the sizes the mapping hands the program."""
    import flops_granite4
    m = _model(granite_cell)
    d, f, di = m["d_model"], m["d_ff"], m["ssm_inner"]
    assert (d, f, di, m["ssm_heads"], m["ssm_groups"], m["ssm_state"],
            m["head_dim"], m["n_kv_heads"], m["n_heads"]) \
        == (2048, 8192, 4096, 64, 1, 128, 64, 8, 32)
    width = di + 2 * 128
    mamba = d * (di + width + 64) + 5 * width + 3 * 64 + di + di * d
    ffn = 3 * d * f
    attention = 2 * d * 2048 + 2 * d * 512
    assert (mamba, ffn, attention) == (25_847_232, 50_331_648, 10_485_760)
    assert mamba + ffn + 2 * d == 76_182_976
    assert attention + ffn + 2 * d == 60_821_504
    table = m["vocab"] * d
    whole = 36 * 76_182_976 + 4 * 60_821_504 + table + d
    assert whole == 3_191_396_096
    small = 36 * (5 * width + 192 + di + 2 * d) + 4 * 2 * d + d
    served = 2 * (whole - small) + 4 * small
    assert (small, served) == (1_103_616, 6_384_999_424)
    a_pass = flops_granite4.pass_weight_bytes(**m)
    assert a_pass["always"] == served
    assert a_pass["head"] == 2 * table + 4 * d
    assert (a_pass["expert"], a_pass["routed"]) == (0.0, 0)
    srv = granite_cell.config["serving"]
    state = 4 * (64 * 64 * 128 + 3 * width)
    assert state == 2_149_376 and 36 * state == 77_377_536
    states = srv["slots"] * 36 * state
    kv = srv["pool_blocks"] * srv["block_size"] * 4 * 2 * 8 * 64 * 4
    assert (states, kv) == (3_714_121_728, 4_026_793_984)
    total = served + states + kv
    assert round(total / 1e9, 2) == 14.13 and total > 12e9
    assert total < 15.0 * 2 ** 30
    # in float32 the weights alone would be 12.77 GB
    assert round(4 * whole / 1e9, 2) == 12.77
    assert (m["state_layers"], m["full_layers"], m["n_layers"]) == (36, 4, 40)
    assert (m["embed_scale"], m["residual_scale"], m["logit_scale"],
            m["attn_scale"]) == (12.0, 0.22, 0.125, 0.015625)
    assert m["layer_pattern"].count("mamba2_ffn") == 36 \
        and m["layer_pattern"][5] == "full" and m["ssm_chunk"] == 256
    assert (m["dtype_bytes"], m["state_dtype_bytes"],
            m["cache_dtype_bytes"], m["weight_dtype"]) == (2, 4, 4,
                                                           "bfloat16")


def test_what_the_mapping_cannot_map_is_refused(granite_cell):
    from kinds import _model_granite4 as mapping
    for wrong in (dict(num_local_experts=8), dict(num_experts_per_tok=2),
                  dict(position_embedding_type="rope"),
                  dict(attention_bias=True), dict(mamba_proj_bias=True),
                  dict(mamba_conv_bias=False),
                  dict(tie_word_embeddings=False),
                  dict(model_type="nemotron_h")):
        with pytest.raises(ValueError, match="Granite 4.0-H"):
            mapping.sizes(dict(granite_cell.config, **wrong))


def _obs(model, steps=100, slots=48, rows=2600):
    """A window's counters at the issue's contexts: every slot live at
    2.6 k rows."""
    return dict(model=model, decode_steps=steps, block_size=16,
                state_slot_steps=36 * steps * slots,
                paged_live_pages=steps * slots * -(-rows // 16),
                slots_used_sum=steps * slots,
                slots_capacity_sum=steps * slots)


def test_the_streams_shares_add_up(granite_cell):
    """`readers/granite4_stream.py` on made-up counters at the issue's
    contexts: the shares are of ONE sum, 15.86 GB a step: 6.38 GB of
    weights as served, 7.43 of states, 2.05 of K/V; a parent that counts
    no state reads nothing."""
    import flops_granite4
    model = _model(granite_cell)
    obs = _obs(model)
    ctx = dict(obs=obs, device=dict(platform="tpu", kind="TPU v5 lite"))

    def read(which):
        return common.read_metrics(
            {"m": dict(reader="granite4_stream", params=dict(which=which),
                       unit="%")}, ctx)

    shares = {w: read(w)["m"]["value"] for w in ("state", "kv")}
    parts = flops_granite4.decode_bytes(
        decode_steps=100, paged_live_pages=obs["paged_live_pages"],
        state_slot_steps=obs["state_slot_steps"], block_size=16, **model)
    per_step = {k: v / 100 / 1e9 for k, v in parts.items()}
    assert abs(per_step["state"] - 48 * 2 * 77_377_536 / 1e9) < 1e-9
    assert abs(per_step["state"] - 7.43) < 0.01
    assert abs(per_step["kv"] - 48 * 163 * 16 * 16384 / 1e9) < 1e-9
    assert abs(per_step["kv"] - 2.05) < 0.01
    assert abs(per_step["weights"] - 6.385) < 0.001
    least = sum(per_step.values())
    assert abs(least - 15.86) < 0.02
    for which, share in shares.items():
        assert abs(share - 100 * per_step[which] / least) < 1e-9
    assert 46 < shares["state"] < 48 and 12 < shares["kv"] < 14
    assert read("state_slots")["m"]["value"] == 100.0
    whole = flops_granite4.decode_least_bytes(
        dict(obs, live_rows=obs["paged_live_pages"] * 16), **model)
    assert sum(whole.values()) == sum(parts.values())
    # 19.4 ms a step at the HBM's rate: a ceiling of 2,480 tokens/s
    assert abs(48 / (least * 1e9 / 819e9) - 2480) < 10
    del obs["state_slot_steps"]
    assert read("state") == {}


def test_the_kernels_costs():
    """The state update: 2 x 2,097,152 B a live slot and layer; the
    grouped paged call: 4,096 B a live row and layer (K and V of 8 heads
    of 64)."""
    import flops_granite4
    flops, nbytes = flops_granite4.ssd_update(
        live_slot_steps=48 * 36, ssm_inner=4096, ssm_state=128)
    assert nbytes == 48 * 36 * 2 * 2_097_152
    assert flops / 197e12 < nbytes / 819e9          # the bytes bound it
    flops, nbytes = flops_granite4.paged_full(
        context_tokens=1000, full_layers=4, calls=1, slots=48, heads=32,
        kv_heads=8, head_dim=64)
    assert nbytes >= 1000 * 4 * 4096
    assert flops / 197e12 < nbytes / 819e9
