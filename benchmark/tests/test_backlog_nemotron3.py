"""The backlog of `nemotron3_nano_serve_rollout_reason_s128` and the
cell's entries in the manifest, on the CPU, with numpy and this
directory's generator and loader alone (no JAX, no program):

    python3 -m pytest benchmark/tests -q -p no:cacheprovider

The file's multiset of sizes, its order's determinism from `order_seed`,
ids under the vocabulary, the parameters the issue gave letter for
letter, the catalog's keys, the headroom rule (PERF.md section 7 (8)) at
the rate the cell read on the chip, the cell's own eight entries under
`.nemotron3` (the 22 common clocks it listed under that suffix until PR
52 are the folded entries' now: `test_manifest.py` holds every serve
cell to them), the arithmetic behind `state_stream_share.nemotron3`,
and, with JAX on the CPU, that the plain reference reads an expert at
its PUBLISHED widths whatever its matrices are stored at. Beside
`test_backlogs.py`, `test_backlog_lfm2.py`, `test_backlog_phi4flash.py`
and `test_manifest.py`.
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
CELL = "nemotron3_nano_serve_rollout_reason_s128"
SUFFIX = ".nemotron3"
PROMPTS = [128, 256, 256, 512, 512, 768, 1024, 1024]
OUTPUTS = [2048, 2341, 2633, 2926, 3218, 3511, 3803, 4096]
OWN = ("ssd_update_roofline", "paged_decode_roofline",
       "moe_expert_roofline", "moe_experts_touched", "state_stream_share",
       "state_slot_share", "kv_stream_share", "weight_stream_share")
# (my chip runs, PR 51; PERF.md section 5): serve_tokens_per_s, the median
# of six untraced runs; a pass without its admissions (`decode_s` over the
# window's steps), ms; an admission, ms
MEASURED = (3990.5, 30.29, 42.1)


@pytest.fixture(scope="module")
def nemo_cell():
    return common.Cell(MANIFEST, CELL)


def _requests(cell, seed):
    requests = workload.request_groups(
        cell.traffic, seed, int(cell.traffic["requests"]),
        int(cell.config["vocab_size"]))
    workload.stagger_first(requests, int(cell.config["serving"]["slots"]))
    return requests


def test_the_file_carries_the_issues_parameters(nemo_cell):
    tr, srv = nemo_cell.traffic, nemo_cell.config["serving"]
    assert tr["kind"] == "backlog_mapped_ssd"
    assert workload.lengths_of(tr["prompt_lens"]) == PROMPTS
    assert workload.lengths_of(tr["output_lens"]) == OUTPUTS
    assert sum(PROMPTS) / len(PROMPTS) == 560
    assert sum(OUTPUTS) / len(OUTPUTS) == 3072
    assert (tr["requests"], tr["lead_in_steps"], tr["trace_seconds"]) \
        == (512, 512, 4)
    assert tr["requests"] < tr["queue_depth"]
    assert tr["prefill_buckets"] == [512, 1024]
    assert (srv["slots"], srv["block_size"], srv["pool_blocks"],
            srv["max_new_tokens"], srv["max_context"]) \
        == (128, 16, 40961, 4096, 5120)
    assert srv["pool_blocks"] == srv["slots"] * srv["max_context"] \
        // srv["block_size"] + 1
    assert nemo_cell.chips == 1 and nemo_cell.entry["traffic"] \
        == "rollout_backlog_reason_s128"
    # the check admits at a length that is not its bucket's end, into a
    # slot a shorter sequence used before, and decodes 8 steps
    chk = tr["check"]
    assert chk == {"prompt_len": 1000, "decode_steps": 8,
                   "former_len": 200, "slot": 5}
    assert chk["prompt_len"] not in tr["prefill_buckets"]
    assert chk["prompt_len"] % nemo_cell.config["chunk_size"]


def test_the_configuration_keeps_the_catalogs_keys(nemo_cell):
    cfg = nemo_cell.config
    manifest = common.load_json(MANIFEST)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == nemo_cell.entry["config"])
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
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
        "num_key_value_heads", "mamba_num_heads", "mamba_head_dim",
        "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
        "moe_intermediate_size", "moe_shared_expert_intermediate_size",
        "num_experts_per_tok", "routed_scaling_factor")} == dict(
        hidden_size=2688, head_dim=128, num_attention_heads=32,
        num_key_value_heads=2, mamba_num_heads=64, mamba_head_dim=64,
        n_groups=8, ssm_state_size=128, conv_kernel=4, chunk_size=128,
        moe_intermediate_size=1856,
        moe_shared_expert_intermediate_size=3712, num_experts_per_tok=6,
        routed_scaling_factor=2.5)
    assert len(cfg["hybrid_override_pattern"]) == 52
    assert cfg["hybrid_override_pattern"][:14] == "MEMEM*EMEMEM*E"
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_first"]) == (14, 32, 32768, 0)
    assert cfg["published"]["num_hidden_layers"] == 52
    assert cfg["published"]["n_routed_experts"] == 128
    assert cfg["published"]["vocab_size"] == 131072
    assert set(cfg["reduced_how"]) == set(reduced) | {"sum"}
    assert {"stands_for", "assumed"} <= set(cfg)
    assert set(cfg["harness"]["limits"]) == {"row_max", "rms_max",
                                             "tie_max"}


def test_every_group_is_the_multiset(nemo_cell):
    requests = workload.request_groups(
        nemo_cell.traffic, 7, int(nemo_cell.traffic["requests"]),
        int(nemo_cell.config["vocab_size"]))
    assert len(requests) == 512
    for g in range(0, 512, 8):
        group = requests[g:g + 8]
        assert sorted(len(r["prompt"]) for r in group) == PROMPTS
        assert sorted(r["max_new"] for r in group) == OUTPUTS
        assert all(r["gap_s"] == 0 for r in group)
    pairs = collections.Counter((len(r["prompt"]), r["max_new"])
                                for r in requests[:64])
    assert pairs == collections.Counter(
        (p, o) for p in PROMPTS for o in OUTPUTS)
    srv = nemo_cell.config["serving"]
    assert all(len(r["prompt"]) + r["max_new"] <= srv["max_context"]
               and r["max_new"] <= srv["max_new_tokens"] for r in requests)


@pytest.mark.parametrize("seed", [7, 2147487001])
def test_the_order_comes_from_order_seed_alone(nemo_cell, seed):
    a, b = _requests(nemo_cell, seed), _requests(nemo_cell, seed + 1)
    assert [(len(r["prompt"]), r["max_new"]) for r in a] \
        == [(len(r["prompt"]), r["max_new"]) for r in b]
    assert _requests(nemo_cell, seed) == a            # and is repeatable
    assert a[0]["prompt"] != b[0]["prompt"]           # the ids are the seed's
    vocab = int(nemo_cell.config["vocab_size"])
    assert all(0 <= t < vocab for r in a[:16] for t in r["prompt"])
    assert max(max(r["prompt"]) for r in a[:16]) > vocab // 2


def test_the_backlog_has_its_room(nemo_cell):
    """Twice the measured rate of headroom, at the window's close and
    when the traced seconds end; and the slot model reads what the chip
    read."""
    traffic, slots, seconds = backlog_headroom.cell_files(MANIFEST, CELL)
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


def test_the_cell_lists_its_own_metrics(nemo_cell):
    """What is this architecture's own stays under its suffix, listing
    this cell alone; every common clock is the folded entry's, which
    names the cell (`test_manifest.py` holds that for every serve cell:
    the twins this file used to hold went with PR 52's fold)."""
    manifest = common.load_json(MANIFEST)
    by_name = {e["name"]: e for e in manifest["per_layer"]}
    suffixed = [e for e in manifest["per_layer"]
                if e["name"].endswith(SUFFIX)]
    assert {e["name"] for e in suffixed} == {base + SUFFIX for base in OWN}
    assert len(suffixed) == 8
    assert all(e["workloads"] == [CELL] for e in suffixed)
    for base in OWN:
        entry, spec = by_name[base + SUFFIX], _file(base + SUFFIX)
        assert entry["moves"] == spec["moves"] == "serve_tokens_per_s"
        assert (entry["unit"], entry["layer"]) \
            == (spec["unit"], spec["layer"]) and entry["unit"] == "%"
    for base in ("ssd_update_roofline", "paged_decode_roofline",
                 "moe_expert_roofline"):
        assert by_name[base + SUFFIX]["source"] == "device_trace"
        assert by_name[base + SUFFIX]["layer"] == "kernels"
    mine = [e["name"] for e in manifest["per_layer"]
            if CELL in e["workloads"]]
    assert len(mine) == 8 + 23 and "serve_step_mfu" in mine
    serve = next(e for e in manifest["end_to_end"]
                 if e["name"] == "serve_tokens_per_s")
    assert CELL in serve["workloads"]
    assert set(nemo_cell.end_to_end) == {"serve_tokens_per_s", "setup_s"}


def _model(cell):
    from kinds import _model_nemotron3 as mapping
    sz = mapping.sizes(cell.config)
    return dict(sz, **sz["block"])


def test_the_cut_is_the_issues_arithmetic(nemo_cell):
    """The parameters and bytes of the configuration's `reduced_how`,
    from the sizes the mapping hands the program."""
    m = _model(nemo_cell)
    d, width = m["d_model"], m["ssm_inner"] + 2 * m["ssm_groups"] \
        * m["ssm_state"]
    assert (m["ssm_inner"], width, m["ssm_inner"] + width
            + m["ssm_heads"]) == (4096, 6144, 10304)
    mamba = d * 10304 + 5 * width + 3 * 64 + 4096 + 4096 * d + d
    attn = 2 * d * 4096 + 2 * d * 256 + d
    experts = 32 * 2 * d * 1856 + 2 * d * 3712 + 128 * d + 128 + d
    vocabulary = 2 * 32768 * d + d
    assert round(mamba / 1e6, 2) == 38.74
    assert round(attn / 1e6, 2) == 23.40
    assert round(experts / 1e6, 1) == 339.6
    total = 6 * mamba + 2 * attn + 6 * experts + vocabulary
    assert round(total / 1e6) == 2493 and round(4 * total / 1e9, 2) == 9.97
    srv = nemo_cell.config["serving"]
    state = 4 * (64 * 64 * 128 + 3 * width)
    assert state == 2097152 + 73728
    states = srv["slots"] * 6 * state
    kv = srv["pool_blocks"] * srv["block_size"] * 2 * 2 * 2 * 128 * 4
    assert (round(states / 1e9, 2), round(kv / 1e9, 2)) == (1.67, 2.68)
    assert round((4 * total + states + kv) / 1e9, 1) == 14.3
    assert (m["state_layers"], m["full_layers"], m["expert_layers"]) \
        == (6, 2, 6)
    assert (m["experts_first"], m["experts_held"], m["num_experts"]) \
        == (0, 32, 128)


def test_the_streams_shares_add_up(nemo_cell):
    """`readers/nemotron3_stream.py` on made-up counters at the issue's
    contexts: the three shares are of ONE sum; the arithmetic behind
    `state_stream_share.nemotron3`: 128 slots x 6 layers x 2 x (2,097,152
    + 73,728) B = 3.33 GB of about 13.9; and a parent that counts no
    state reads nothing."""
    import flops_nemotron3
    model = _model(nemo_cell)
    steps, slots, rows = 100, 128, 2048
    pages = steps * slots * rows // 16
    obs = dict(model=model, decode_steps=steps, block_size=16,
               moe_experts_touched=int(0.998 * 32 * 6 * steps),
               moe_layer_steps=6 * steps, paged_live_pages=pages,
               state_slot_steps=6 * steps * slots)
    ctx = dict(obs=obs, device=dict(platform="tpu", kind="TPU v5 lite"))
    shares = {w: common.read_metrics(
        {"m": dict(reader="nemotron3_stream", params=dict(which=w),
                   unit="%")}, ctx)["m"]["value"]
        for w in ("state", "kv")}
    parts = flops_nemotron3.decode_bytes(**{
        k: v for k, v in obs.items() if k not in ("model", "decode_steps")},
        **model)
    per_step = {k: v / steps / 1e9 for k, v in parts.items()}
    assert abs(per_step["state"] - 128 * 6 * 2 * 2170880 / 1e9) < 1e-9
    assert abs(per_step["state"] - 3.33) < 0.01
    assert abs(per_step["kv"] - 128 * 2048 * 4096 / 1e9) < 1e-9
    assert abs(per_step["weights"] - 9.6) < 0.1
    least = sum(per_step.values())
    assert abs(least - 13.9) < 0.2
    for which, share in shares.items():
        assert abs(share - 100 * per_step[which] / least) < 1e-9
    assert 23 < shares["state"] < 25 and 6.5 < shares["kv"] < 8.5
    # every live slot's state moved once a state layer a step
    obs["slots_capacity_sum"] = steps * slots
    spec = {"m": dict(reader="nemotron3_stream",
                      params=dict(which="state_slots"), unit="%")}
    assert common.read_metrics(spec, ctx)["m"]["value"] == 100.0
    # a step in flight at the window's close: counted when dispatched,
    # `decode_steps` when emitted; never over 100
    obs["state_slot_steps"] += 6 * slots
    assert common.read_metrics(spec, ctx)["m"]["value"] == 100.0
    obs["state_slot_steps"] -= 6 * slots + 6 * 32   # 32 idle slot-steps
    assert abs(common.read_metrics(spec, ctx)["m"]["value"]
               - 100 * (1 - 32 / (steps * slots))) < 1e-9
    del obs["state_slot_steps"]
    assert common.read_metrics(
        {"m": dict(reader="nemotron3_stream", params=dict(which="state"),
                   unit="%")}, ctx) == {}


def test_the_kernels_costs():
    """The state update: 2 x 2,097,152 B a live slot and layer; an
    expert: TWO matrices; the paged call: 2,048 B a live row."""
    import flops_moe
    import flops_nemotron3
    sizes = dict(ssm_inner=4096, ssm_state=128)
    flops, nbytes = flops_nemotron3.ssd_update(live_slot_steps=128 * 6,
                                               **sizes)
    assert nbytes == 128 * 6 * 2 * 2097152
    assert flops / 197e12 < nbytes / 819e9          # the bytes bound it
    two = flops_nemotron3.decode_experts(
        assignments=192, experts_touched=32, d_model=2688, d_ff=1856)
    three = flops_moe.decode_experts(
        assignments=192, experts_touched=32, d_model=2688, d_ff=1856)
    assert two == (three[0] * 2 / 3, three[1] * 2 / 3)
    assert two[1] == 32 * 2 * 2688 * 1856 * 4
    _, nbytes = flops_nemotron3.paged_full(
        context_tokens=1000, full_layers=2, calls=1, slots=128, heads=32,
        kv_heads=2, head_dim=128)
    assert nbytes >= 2 * 1000 * 2048


# -- the reference reads an expert at its published widths (PR 52) ----------

@pytest.fixture(scope="module")
def small_reference():
    """Two E layers about an attention layer at small PUBLISHED widths
    (d 48, f 40, 4 experts, top-2, a shared expert of 80), float32 on
    the CPU: (reference module, weights, ids, Hyper, its logits)."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    import numpy as np
    import reference_nemotron3 as ref
    rng = np.random.RandomState(52)
    d, f, experts, vocab, seq = 48, 40, 4, 64, 24

    def w(*shape):
        return jnp.asarray(rng.randn(*shape).astype("float32")
                           / np.sqrt(shape[-2]))

    def experts_layer():
        return dict(ln=jnp.ones(d), router=w(d, experts),
                    router_bias=jnp.asarray(
                        0.03 * rng.randn(experts).astype("float32")),
                    up=w(experts, d, f), down=w(experts, f, d),
                    shared_up=w(d, 2 * f), shared_down=w(2 * f, d))

    weights = dict(
        tok_emb=w(vocab, d), ln_f=jnp.ones(d), head=w(d, vocab),
        layers=[experts_layer(),
                dict(ln=jnp.ones(d), q=w(d, 32), k=w(d, 16), v=w(d, 16),
                     out=w(32, d)),
                experts_layer()])
    hp = ref.Hyper(("experts", "attention", "experts"), 4, 2, 8, 4, 8, 2,
                   8, 2)
    ids = rng.randint(0, vocab, seq)
    return ref, weights, ids, hp, np.asarray(ref.logits(weights, ids, hp))


def _stored(weights, up_cols=0, down_rows=0, down_cols=0, fill=0.0):
    """The same weights with every routed expert's matrices stored
    wider: `fill` behind the published width."""
    import jax.numpy as jnp
    layers = []
    for layer in weights["layers"]:
        layer = dict(layer)
        if "up" in layer:
            layer["up"] = jnp.pad(layer["up"],
                                  ((0, 0), (0, 0), (0, up_cols)),
                                  constant_values=fill)
            layer["down"] = jnp.pad(
                layer["down"], ((0, 0), (0, down_rows), (0, down_cols)),
                constant_values=fill)
        layers.append(layer)
    return dict(weights, layers=layers)


@pytest.mark.parametrize("padding", [
    dict(up_cols=24, down_rows=24),                  # the hidden width: PR 51
    dict(down_cols=16),                              # the model width alone
    dict(up_cols=24, down_rows=24, down_cols=16),    # both sides
    dict(up_cols=88, down_rows=88, down_cols=80),    # whole tiles of 128
], ids=["hidden", "model", "both", "tiles_of_128"])
def test_the_reference_reads_an_expert_at_its_published_widths(
        small_reference, padding):
    """Zeros behind the published width, on either side of an expert,
    give the logits of the unpadded weights BIT FOR BIT: a program may
    store its experts in whole tiles and be checked on its own arrays."""
    import numpy as np
    ref, weights, ids, hp, want = small_reference
    got = np.asarray(ref.logits(_stored(weights, **padding), ids, hp))
    assert got.shape == want.shape and np.array_equal(got, want)
    u = np.asarray(weights["tok_emb"])[:8]
    routed, shared = ref.experts_layer(
        _stored(weights, **padding)["layers"][0], u, hp)
    routed0, shared0 = ref.experts_layer(weights["layers"][0], u, hp)
    assert routed.shape == (8, 48) and np.array_equal(
        np.asarray(routed), np.asarray(routed0))
    assert np.array_equal(np.asarray(shared), np.asarray(shared0))


def test_the_reference_does_not_hide_a_padding_that_is_computed_with(
        small_reference):
    """A NONZERO value in the hidden padding (a column of `up` with its
    row of `down`) changes the logits: were the program to compute with
    its padding, the check would see it. (Columns of `down` past the
    model width are no part of the model: whoever computes them drops
    them, the reference too.)"""
    import numpy as np
    ref, weights, ids, hp, want = small_reference
    got = np.asarray(ref.logits(
        _stored(weights, up_cols=24, down_rows=24, fill=0.5), ids, hp))
    assert np.max(np.abs(got - want)) > 0.1 * np.std(want)
    # one side alone nonzero: relu(a)^2 of a nonzero column meets rows
    # of zeros, or rows of nonzeros meet relu(0)^2: nothing changes
    import jax.numpy as jnp
    one_sided = _stored(weights, up_cols=24, down_rows=24)
    one_sided["layers"][0]["up"] = jnp.pad(
        weights["layers"][0]["up"], ((0, 0), (0, 0), (0, 24)),
        constant_values=0.5)
    assert np.array_equal(
        np.asarray(ref.logits(one_sided, ids, hp)), want)
