"""The backlog of `glm5_serve_rollout_12k_lsel` and the cell's entries in
the manifest, on the CPU, with numpy and this directory's generator and
loader alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The file's multiset of sizes, its order's determinism from `order_seed`,
ids under the vocabulary, the parameters the issue gave letter for letter
(but the slots: 12, with the reading that forced it in the file), the
catalog's keys but the five in `reduced`, the cell's own entries under
`.glm5` beside the folded common clocks that name it last, the arithmetic
of the configuration's file from the sizes the mapping hands the program,
and the pricing of its two kernels and of a step's least bytes.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, os.path.join(HERE, "tools")]

import common  # noqa: E402
import workload  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "glm5_serve_rollout_12k_lsel"
SUFFIX = ".glm5"
PROMPTS = [6144, 6144, 8192, 8192, 8192, 12288, 12288, 12288]
OUTPUTS = [1024, 1170, 1317, 1463, 1609, 1755, 1902, 2048]
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
OWN = ("paged_sparse_latent_roofline", "paged_indexer_roofline",
       "latent_cache_stream_share", "moe_expert_roofline",
       "moe_experts_touched", "moe_weight_stream_share",
       "prefill_selected_attention_share")
# entries of other architectures' names whose files fit this cell as they
# are: it is appended to their lists
BORROWED = ("sparse_select_ms.keye", "sparse_select_share.keye",
            "moe_held_pair_share.cmda")


@pytest.fixture(scope="module")
def glm5_cell():
    return common.Cell(MANIFEST, CELL)


def _requests(cell, seed):
    requests = workload.request_groups(
        cell.traffic, seed, int(cell.traffic["requests"]),
        int(cell.config["vocab_size"]))
    workload.stagger_first(requests, int(cell.config["serving"]["slots"]))
    return requests


def test_the_file_carries_the_issues_parameters(glm5_cell):
    tr, srv = glm5_cell.traffic, glm5_cell.config["serving"]
    assert tr["kind"] == "backlog_mapped_sel_held"
    assert workload.lengths_of(tr["prompt_lens"]) == PROMPTS
    assert workload.lengths_of(tr["output_lens"]) == OUTPUTS
    assert sum(PROMPTS) / len(PROMPTS) == 9216
    assert abs(sum(OUTPUTS) / len(OUTPUTS) - 1536) < 1
    assert min(PROMPTS) > glm5_cell.config["index_topk"] == 2048
    assert (tr["requests"], tr["queue_depth"], tr["lead_in_steps"],
            tr["trace_seconds"]) == (128, 160, 256, 4)
    assert tr["prefill_buckets"] == [6144, 8192, 12288]
    assert tr["check"] == {"prompt_len": 3072, "decode_steps": 4}
    # 12 slots where the issue asked for 16, the reading in the file
    assert (srv["dtype"], srv["slots"], srv["block_size"],
            srv["pool_blocks"], srv["max_new_tokens"],
            srv["max_context"]) == ("float32", 12, 16, 10753, 2048, 14336)
    assert srv["pool_blocks"] == srv["slots"] * srv["max_context"] \
        // srv["block_size"] + 1
    assert max(PROMPTS) + max(OUTPUTS) == srv["max_context"]
    assert "16,148,352,000" in glm5_cell.config["reduced_how"]["cache"]
    assert glm5_cell.chips == 1 and glm5_cell.entry["traffic"] \
        == "rollout_backlog_12k_lsel"


def test_the_configuration_keeps_the_catalogs_keys(glm5_cell):
    cfg = glm5_cell.config
    manifest = common.load_json(MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == glm5_cell.entry["config"])
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"]
    assert entry["file"] == "benchmark/configs/glm-5-serve.json"
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(line) for line in f if line.strip()]
        row = next(r for r in rows if r["name"] == "GLM-5")
        assert row["source_url"] == cfg["source"]
        for key, value in row["config"].items():
            if key in REDUCED:
                assert cfg["published"][key] == value, key
            else:
                assert cfg[key] == value, key
    assert {k: cfg[k] for k in REDUCED} == dict(
        num_hidden_layers=5, first_k_dense_replace=1, n_routed_experts=8,
        vocab_size=19360, num_nextn_predict_layers=0)
    # the widths, whatever the catalog file says tomorrow
    assert {k: cfg[k] for k in (
        "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim", "v_head_dim",
        "index_n_heads", "index_head_dim", "index_topk",
        "moe_intermediate_size", "intermediate_size", "num_experts_per_tok",
        "routed_scaling_factor", "n_shared_experts")} == dict(
        hidden_size=6144, num_attention_heads=64, q_lora_rank=2048,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        qk_head_dim=256, v_head_dim=256, index_n_heads=32,
        index_head_dim=128, index_topk=2048, moe_intermediate_size=2048,
        intermediate_size=12288, num_experts_per_tok=8,
        routed_scaling_factor=2.5, n_shared_experts=1)
    assert cfg["published"]["held_experts"] == {"first": 0, "count": 8}
    assert {"stands_for", "assumed", "published", "reduced_how"} <= set(cfg)
    assert {"dtype", "weights", "e_score_correction_bias", "indexer", "mtp",
            "precision", "cache_row", "max_context", "head_dim",
            "low_rank_gain"} <= set(cfg["assumed"])
    assert set(cfg["harness"]["limits"]) == {"row_max", "rms_max", "tie_max",
                                             "sel_tie_max"}
    assert set(cfg["harness"]["limits_why"]) >= {
        "readings", "row_max", "rms_max", "tie_max", "sel_tie_max"}
    assert (cfg["harness"]["mapping"], cfg["harness"]["reference"],
            cfg["harness"]["flops"]) == (
        "_model_glm5", "reference_glm5", "flops_glm5")


def test_every_group_is_the_multiset(glm5_cell):
    requests = workload.request_groups(
        glm5_cell.traffic, 7, 128, int(glm5_cell.config["vocab_size"]))
    assert len(requests) == 128
    for g in range(0, 128, 8):
        group = requests[g:g + 8]
        assert sorted(len(r["prompt"]) for r in group) == PROMPTS
        assert sorted(r["max_new"] for r in group) == OUTPUTS
        assert all(r["gap_s"] == 0 for r in group)
    srv = glm5_cell.config["serving"]
    assert all(len(r["prompt"]) + r["max_new"] <= srv["max_context"]
               and r["max_new"] <= srv["max_new_tokens"] for r in requests)


@pytest.mark.parametrize("seed", [7, 2147487001])
def test_the_order_comes_from_order_seed_alone(glm5_cell, seed):
    a, b = _requests(glm5_cell, seed), _requests(glm5_cell, seed + 1)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] \
        == [(len(r["prompt"]), r["max_new"]) for r in b]
    assert _requests(glm5_cell, seed) == a            # and is repeatable
    assert a[0]["prompt"] != b[0]["prompt"]           # the ids are the seed's
    vocab = int(glm5_cell.config["vocab_size"])
    assert all(0 <= t < vocab for r in a[:12] for t in r["prompt"])
    assert max(max(r["prompt"]) for r in a[:12]) > vocab // 2


def _file(name):
    return common.load_json(
        os.path.join(HERE, "layer_metrics", name + ".json"))


def test_the_cell_lists_its_own_metrics(glm5_cell):
    """What is this architecture's own stays under its suffix, listing
    this cell alone and LAST in the manifest (appended: nothing put in
    the middle); every common clock is the folded entry's, which names
    the cell last; no twin of an entry that was there."""
    manifest = common.load_json(MANIFEST)
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    suffixed = [e for e in manifest["per_layer"]
                if e["name"].endswith(SUFFIX)]
    assert [e["name"] for e in suffixed] == [b + SUFFIX for b in OWN]
    assert len(OWN) <= 8
    assert manifest["per_layer"][-len(OWN):] == suffixed
    assert all(e["workloads"] == [CELL] for e in suffixed)
    for base in OWN:
        entry, spec = by_name[base + SUFFIX], _file(base + SUFFIX)
        assert entry["moves"] == spec["moves"] == "serve_tokens_per_s"
        assert (entry["unit"], entry["layer"]) \
            == (spec["unit"], spec["layer"]) and entry["unit"] == "%"
    for base, kernel, cost in (
            (OWN[0], "paged_sparse_latent_attention", "paged_sparse_latent"),
            (OWN[1], "paged_index_scores", "paged_index")):
        assert by_name[base + SUFFIX]["source"] == "device_trace"
        assert by_name[base + SUFFIX]["layer"] == "kernels"
        spec = _file(base + SUFFIX)
        assert (spec["reader"], spec["params"]["module"],
                spec["params"]["match"], spec["params"]["cost"]) \
            == ("kernel_roofline_of", "flops_glm5", [kernel], cost)
    inner = _file("moe_expert_roofline" + SUFFIX)
    assert (inner["reader"], inner["params"]["reader"],
            inner["params"]["params"]["reader"]) \
        == ("held_experts", "expert_layers", "expert_roofline")
    assert inner["params"]["params"]["params"]["match"] \
        == ["ragged-dot", "expert_grouped_matmul"]
    shared = [e for e in manifest["per_layer"]
              if CELL in e["workloads"] and not e["name"].endswith(SUFFIX)]
    assert all(e["workloads"][-1] == CELL for e in shared)
    names = [e["name"] for e in shared]
    assert "serve_step_mfu" in names and "check_s.rollout" in names
    assert set(BORROWED) <= set(names)
    assert len(shared) == 25 + len(BORROWED)
    assert manifest["workloads"][-1]["name"] == CELL
    assert manifest["configs"][-1]["name"] == glm5_cell.entry["config"]
    serve = next(e for e in manifest["end_to_end"]
                 if e["name"] == "serve_tokens_per_s")
    assert serve["workloads"][-1] == CELL
    assert set(glm5_cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}


def _model(cell):
    from kinds import _model_glm5 as mapping
    sz = mapping.sizes(cell.config)
    return dict(sz, **sz["block"])


def test_the_file_holds_the_issues_arithmetic(glm5_cell):
    """The parameters and bytes of the configuration's `reduced_how`,
    from the sizes the mapping hands the program."""
    import flops_glm5
    m = _model(glm5_cell)
    d = m["d_model"]
    attention = d * 2048 + 2048 * 64 * 256 + d * 576 + 512 * 64 * 448 \
        + 64 * 256 * d
    indexer = 2048 * 32 * 128 + d * 128 + 2 * 128 + d * 32
    assert (attention, indexer) == (165_019_648, 9_371_904)
    outside = attention + 2560 + indexer + 3 * d * 2048 + d * 256 + 256 \
        + 2 * d
    assert outside == 213_728_256
    expert_layer = outside + 8 * 3 * d * 2048
    dense_layer = attention + 2560 + indexer + 2 * d + 3 * d * 12288
    table = 2 * 19360 * d + d
    assert (expert_layer, dense_layer, table) \
        == (515_718_144, 400_898_816, 237_901_824)
    whole = dense_layer + 4 * expert_layer + table
    how = glm5_cell.config["reduced_how"]
    assert whole == how["parameters"] == 2_701_673_216
    assert 4 * whole == how["weight_bytes"] == 10_806_692_864
    # what one pass reads whatever it routes: everything but the routed
    # experts and the embedding table
    a_pass = flops_glm5.pass_weight_bytes(**m)
    assert a_pass["always"] == 4 * (whole - 4 * 8 * 3 * d * 2048
                                    - 19360 * d)
    assert a_pass["head"] == 4 * (19360 * d + d)
    assert (a_pass["expert"], a_pass["routed"]) == (4 * 3 * d * 2048, 0)
    srv = glm5_cell.config["serving"]
    pools = srv["pool_blocks"] * srv["block_size"] * 5 * 4 * (640 + 128)
    assert pools == 2_642_657_280
    total = 4 * whole + pools
    assert round(total / 1e9, 2) == 13.45 and total < 15.0 * 2 ** 30
    assert total > 0.25 * 16e9
    assert (m["n_layers"], m["dense_layers"], m["num_experts"],
            m["experts_held"], m["experts_first"], m["row_chunk"]) \
        == (5, 1, 256, 8, 0, 2048)


def test_what_the_mapping_cannot_map_is_refused(glm5_cell):
    from kinds import _model_glm5 as mapping
    for wrong in (dict(n_group=8), dict(q_lora_rank=None),
                  dict(num_nextn_predict_layers=1),
                  dict(reduced=REDUCED[:4]),
                  dict(rope_parameters={"rope_theta": 1e6,
                                        "rope_type": "yarn"}),
                  dict(scoring_func="softmax"),
                  dict(tie_word_embeddings=True), dict(head_dim=128)):
        with pytest.raises(ValueError):
            mapping.sizes(dict(glm5_cell.config, **wrong))


def test_the_kernels_costs_and_a_steps_least_bytes(glm5_cell):
    """A selected latent row: 2,560 B as stored for 64 x 2 x (576 + 512)
    operations, 54 a byte, under the chip's ridge of 240: the bytes bound
    it. A step of 12 slots at 10 k rows: 0.6 GB of caches beside 7.6 GB
    of weights."""
    import flops_glm5
    m = _model(glm5_cell)
    flops, nbytes = flops_glm5.paged_sparse_latent(
        selected_rows=12 * 2048, layers=5, calls=1, slots=12, heads=64,
        row_floats=576, value_floats=512)
    rows = 12 * 2048 * 5
    assert flops == rows * 64 * 2 * (576 + 512)
    assert nbytes == rows * 2560 + 4 * 5 * 12 * 64 * (576 + 512)
    assert 50 < flops / (rows * 2560) < 58
    assert flops / 197e12 < nbytes / 819e9
    flops, nbytes = flops_glm5.paged_index(
        context_tokens=12 * 10000, layers=5, calls=1, slots=12,
        index_heads=32, index_dim=128)
    assert nbytes >= 12 * 10000 * 5 * 4 * 128
    assert 15 < flops / nbytes < 17
    counts = dict(moe_experts_touched=4 * 3, moe_layer_steps=4,
                  sparse_live_rows=12 * 10000,
                  sparse_selected_rows=12 * 2048)
    parts = flops_glm5.decode_least_bytes(counts, **m)
    assert parts["states"] == 0.0
    assert abs(parts["cache"] / 1e9 - 0.59) < 0.01
    assert abs(parts["weights"] / 1e9 - 7.31) < 0.01
    # the stream reader on the same counters
    ctx = dict(obs=dict(counts, model=m),
               device=dict(platform="tpu", kind="TPU v5 lite"))
    got = common.read_metrics(
        {"m": dict(reader="glm5_stream", params=dict(which="cache"),
                   unit="%")}, ctx)["m"]["value"]
    assert abs(got - 100 * parts["cache"]
               / (parts["cache"] + parts["weights"])) < 1e-9
    assert 7 < got < 8
    # a parent that counts no selected rows reads nothing
    del ctx["obs"]["sparse_selected_rows"]
    assert common.read_metrics(
        {"m": dict(reader="glm5_stream", params=dict(which="cache"),
                   unit="%")}, ctx) == {}
    # an admission's share of the traced seconds: 0 where none fell in
    red = dict(busy_s=2.0, op_seconds={"fusion": 1.5,
                                       "scaled_dot_product_attention": 0.4})
    spec = {"m": dict(reader="glm5_stream", unit="%", params=dict(
        which="scope", match=["scaled_dot_product_attention"]))}
    assert common.read_metrics(spec, dict(ctx, reduced=red))["m"][
        "value"] == 20.0
    red["op_seconds"].pop("scaled_dot_product_attention")
    assert common.read_metrics(spec, dict(ctx, reduced=red))["m"][
        "value"] == 0.0
